"""fit_s: all window time over the fits completed in it, one closed-loop
client."""


def read(run):
    done = run.done
    return run.window_s / len(done) if done else None
