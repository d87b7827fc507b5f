"""The chip benchmark of the deCSVM solver: one cell per run, driven by the
files under ``configs/``, ``traffic/``, ``metrics/``, ``ops/`` and
``checks/``.  ``run.py`` is the entry point."""
