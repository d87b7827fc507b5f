import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove that every (architecture x input shape x mesh)
combination lowers AND compiles under the production meshes, and dump the
roofline inputs (memory analysis, FLOPs, bytes, collective bytes).

The two lines above MUST stay first: jax locks the device count on first
initialization, and the production meshes need 512 host placeholder devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b \
      --shape train_4k --mesh single --out results/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import functools
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.data.synthetic import SHAPES, input_specs
from repro.launch import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.launch.serve import make_jitted_serve_step
from repro.launch.train import make_jitted_train_step
from repro.models import model
from repro.optim import AdamWConfig, adamw_init

# --- TPU v5e hardware constants (roofline denominators) ---
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_COLL_OP_RE = re.compile(
    r"\s(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in (post-SPMD) HLO text.

    Line-based: `%name = <result-type(s)> <op>(operands)` — handles both
    GSPMD modules (hyphenated LHS names) and shard_map manual lowering
    (underscored LHS names).  ``-done`` halves of async pairs are skipped.
    """
    out = {}
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        m = _COLL_OP_RE.search(line)
        if m is None or "-done(" in line:
            continue
        kind = m.group(1)
        result_seg = line.split("=", 1)[1][:m.start() - line.index("=")]
        # fall back to everything before the op token
        result_seg = line.split("=", 1)[1].split(f" {kind}")[0]
        total = 0
        for dtype, dims in _SHAPE_RE.findall(result_seg):
            if dtype not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dtype]
        if total:
            out[kind] = out.get(kind, 0) + total
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def _mode_for(cfg, shape_name: str) -> str:
    if shape_name == "long_500k":
        return "long"
    return {"train_4k": "train", "prefill_32k": "prefill",
            "decode_32k": "decode"}[shape_name]


def _analyze(compiled):
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    return flops, bytes_acc, coll


def _scan_units(cfg):
    """(kinds-in-one-scan-body, trip_count) per scanned stack.

    XLA's cost_analysis counts a while-loop body ONCE regardless of trip
    count, so the dry-run compiles one body at identical shapes/shardings
    and scales by (trips - 1).
    """
    from repro.models import blocks
    kinds = blocks.block_kinds(cfg)
    units = []
    if len(set(kinds)) == 1:
        units.append(((kinds[0],), cfg.num_layers))
    else:
        pat = cfg.block_pattern
        units.append((tuple(pat), cfg.num_layers // len(pat)))
        # tail layers are python-unrolled in the model: already fully counted
    if cfg.is_encoder_decoder:
        units.append((("enc",), cfg.num_encoder_layers))
    return units


def _layer_cost(cfg, mesh, sh, mode: str, fsdp: bool = True,
                ep: bool = False):
    """Compile single scan-body units; return (flops, bytes, coll) to ADD."""
    import numpy as _np
    from repro.models import blocks
    from repro.models.shardctx import constrain

    dtype = jnp.dtype(cfg.param_dtype)
    P = jax.sharding.PartitionSpec
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    B = sh.global_batch
    S_dec = sh.seq_len if sh.kind != "decode" else 1
    if cfg.frontend == "vision" and sh.kind != "decode":
        S_dec = sh.seq_len  # media prefix + text == seq_len total
    window = None
    if cfg.sliding_window is not None:
        window = cfg.sliding_window
    elif mode == "long":
        window = cfg.long_context_window
    enc_len = min(cfg.frontend_len or 128, max(sh.seq_len // 4, 16))

    add_f = add_b = 0.0
    add_c = {}

    def accumulate(flops, bytes_, coll, times):
        nonlocal add_f, add_b, add_c
        add_f += flops * times
        add_b += bytes_ * times
        for k, v in coll.items():
            add_c[k] = add_c.get(k, 0) + v * times

    for kinds_in_body, trips in _scan_units(cfg):
        if trips <= 1:
            continue
        is_enc = kinds_in_body == ("enc",)
        S = enc_len if is_enc else S_dec
        x_struct = jax.ShapeDtypeStruct((B, S, cfg.d_model), dtype)
        cross = cfg.is_encoder_decoder and not is_enc
        lp_structs = tuple(
            jax.eval_shape(functools.partial(
                blocks.init_block, cfg=cfg,
                kind=("attn" if is_enc else k), dtype=dtype, cross=cross),
                jax.random.PRNGKey(0))
            for k in kinds_in_body)
        # decode weights are never FSDP-sharded (see make_jitted_serve_step)
        body_fsdp = fsdp and sh.kind != "decode"
        lp_specs = tuple(shd.param_pspecs(lp, mesh, fsdp=body_fsdp,
                                          expert_parallel=ep)
                         for lp in lp_structs)
        enc_struct = (jax.ShapeDtypeStruct((B, enc_len, cfg.d_model), dtype)
                      if cross and sh.kind != "decode" else None)

        if sh.kind == "decode":
            caches = tuple(
                jax.eval_shape(functools.partial(
                    blocks.init_block_cache, cfg, k, B, sh.seq_len, dtype,
                    window=window))
                for k in kinds_in_body)
            cache_specs = tuple(shd.cache_pspecs(c, cfg, mesh)
                                for c in caches)
            cross_kv = None
            if cross:
                cross_kv = jax.eval_shape(lambda: {
                    "k": jnp.zeros((B, enc_len, cfg.num_kv_heads,
                                    cfg.head_dim), dtype),
                    "v": jnp.zeros((B, enc_len, cfg.num_kv_heads,
                                    cfg.head_dim), dtype)})

            def body(lps, x1, cs, ckv):
                pos = jnp.asarray(sh.seq_len // 2, jnp.int32)
                new_cs = []
                for k, lp, c in zip(kinds_in_body, lps, cs):
                    x1, nc = blocks.block_decode(
                        lp, x1, c, pos, cfg, k,
                        window=window if k == "attn" else None,
                        cross_kv=ckv)
                    new_cs.append(nc)
                return x1, tuple(new_cs)

            jb = jax.jit(body, in_shardings=(
                tuple(shd.to_named(s, mesh) for s in lp_specs),
                shd.to_named(P(dp, None, None) if B % 2 == 0 else P(), mesh),
                tuple(shd.to_named(s, mesh) for s in cache_specs),
                (shd.to_named(shd.cache_pspecs(cross_kv, cfg, mesh), mesh)
                 if cross_kv is not None else None),
            ))
            with jax.set_mesh(mesh):
                comp = jb.lower(lp_structs, x_struct, caches,
                                cross_kv).compile()
        else:
            def fwd(lps, x, enc_out):
                for k, lp in zip(kinds_in_body, lps):
                    kk = "attn" if is_enc else k
                    x, aux = blocks.block_forward(
                        lp, x, cfg, kk,
                        causal=not is_enc,
                        window=window if kk == "attn" else None,
                        enc_out=enc_out)
                    x = constrain(x, "data", None, None)
                return x

            if sh.kind == "train":
                # remat-faithful calibration: wrap in the same checkpoint
                # policy as the model's layer scan so backward recompute
                # (and its collectives) are counted.
                from repro.models.model import remat_policy as _rp
                fwd_ckpt = jax.checkpoint(fwd, policy=_rp(cfg))

                def scalar(lps, x, enc_out):
                    return jnp.sum(fwd_ckpt(lps, x, enc_out)
                                   .astype(jnp.float32))
                f = jax.grad(scalar, argnums=(0, 1))
            else:
                f = fwd
            jb = jax.jit(f, in_shardings=(
                tuple(shd.to_named(s, mesh) for s in lp_specs),
                shd.to_named(P(dp, None, None), mesh),
                (shd.to_named(P(dp, None, None), mesh)
                 if enc_struct is not None else None),
            ))
            with jax.set_mesh(mesh):
                comp = jb.lower(lp_structs, x_struct, enc_struct).compile()

        f_, b_, c_ = _analyze(comp)
        accumulate(f_, b_, c_, trips - 1)
    add_c["total"] = sum(v for k, v in add_c.items() if k != "total")
    return add_f, add_b, add_c


def run_one(arch: str, shape_name: str, mesh_kind: str, verbose: bool = True,
            variant: str = "baseline"):
    """Lower + compile one (arch, shape, mesh) combo; return roofline record.

    variant: "baseline" (FSDPxTP 2D weights) | "zero1" (weights model-only,
    moments sharded) | "ep" (expert-parallel MoE) | "zero1_ep".
    """
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    cfg = configs.get(arch)
    import dataclasses as _dc
    if "scatter" in variant:
        cfg = _dc.replace(cfg, moe_routing="scatter")
    if "rematdots" in variant:
        cfg = _dc.replace(cfg, remat_policy="dots")
    if "rematnames" in variant:
        cfg = _dc.replace(cfg, remat_policy="names")
    if "attnshard" in variant:
        cfg = _dc.replace(cfg, attn_act_shard=True)
    if "seqpar" in variant:
        cfg = _dc.replace(cfg, seq_parallel=True)
    if "kv8" in variant:
        cfg = _dc.replace(cfg, kv_cache_dtype="int8")
    sh = SHAPES[shape_name]
    mode = _mode_for(cfg, shape_name)
    fsdp = "zero1" not in variant
    ep = "ep" in variant.split("_")
    t0 = time.time()

    if sh.kind == "train":
        batch_struct = input_specs(cfg, sh)
        jitted, _ = make_jitted_train_step(cfg, AdamWConfig(), mesh,
                                           batch_struct, mode=mode,
                                           fsdp=fsdp, expert_parallel=ep)
        params_struct = jax.eval_shape(
            functools.partial(model.init_params, cfg), jax.random.PRNGKey(0))
        opt_struct = jax.eval_shape(adamw_init, params_struct)
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_struct, opt_struct, batch_struct)
    elif sh.kind == "prefill":
        batch_struct = input_specs(cfg, sh)

        def prefill(params, batch):
            logits, _ = model.forward(params, batch, cfg, mode="prefill")
            return jnp.argmax(logits, axis=-1)

        params_struct = jax.eval_shape(
            functools.partial(model.init_params, cfg), jax.random.PRNGKey(0))
        p_specs = shd.param_pspecs(params_struct, mesh, fsdp=fsdp,
                                   expert_parallel=ep)
        b_specs = shd.batch_pspecs(batch_struct, mesh)
        jitted = jax.jit(prefill,
                         in_shardings=(shd.to_named(p_specs, mesh),
                                       shd.to_named(b_specs, mesh)))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_struct, batch_struct)
    else:  # decode
        jitted, _ = make_jitted_serve_step(cfg, mesh, sh.global_batch,
                                           sh.seq_len, mode=mode)
        params_struct = jax.eval_shape(
            functools.partial(model.init_params, cfg), jax.random.PRNGKey(0))
        cache_struct = jax.eval_shape(
            functools.partial(model.init_cache, cfg, sh.global_batch,
                              sh.seq_len, mode))
        tok = jax.ShapeDtypeStruct((sh.global_batch,), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_struct, cache_struct, tok, pos)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    flops_raw, bytes_raw, coll_raw = _analyze(compiled)

    # Scan-trip-count correction: XLA cost_analysis counts while-loop bodies
    # once; compile one body at identical shapes/shardings and scale.
    try:
        add_f, add_b, add_c = _layer_cost(cfg, mesh, sh, mode, fsdp=fsdp,
                                          ep=ep)
    except Exception:  # noqa: BLE001 — record raw-only if calibration fails
        traceback.print_exc()
        add_f, add_b, add_c = 0.0, 0.0, {"total": 0}

    flops = flops_raw + add_f
    bytes_acc = bytes_raw + add_b
    coll = dict(coll_raw)
    for k, v in add_c.items():
        coll[k] = coll.get(k, 0) + v
    # cost_analysis is per-device-module on CPU backend after SPMD
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_acc / HBM_BW
    collective_s = coll["total"] / ICI_BW

    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    n_model = cfg.active_params() if cfg.arch_type == "moe" else cfg.n_params()
    sh_obj = SHAPES[shape_name]
    tokens = (sh_obj.global_batch * sh_obj.seq_len
              if sh_obj.kind != "decode" else sh_obj.global_batch)
    model_flops = 6.0 * n_model * tokens if sh_obj.kind == "train" \
        else 2.0 * n_model * tokens
    useful_ratio = model_flops / (flops * n_chips) if flops else 0.0

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "variant": variant,
        "chips": int(n_chips),
        "ok": True,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0)
            + (getattr(mem, "argument_size_in_bytes", 0) or 0),
        },
        "cost_analysis": {"flops": flops, "bytes_accessed": bytes_acc,
                          "flops_raw": flops_raw, "bytes_raw": bytes_raw,
                          "scan_correction_flops": add_f},
        "collective_bytes": coll,
        "collective_bytes_raw": coll_raw,
        "roofline": {**terms, "dominant": dominant,
                     "model_flops_total": model_flops,
                     "hlo_flops_per_chip": flops,
                     "useful_flops_ratio": useful_ratio},
    }
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_kind}] "
              f"compile={t_compile:.1f}s "
              f"mem(temp)={rec['memory_analysis']['temp_bytes']} "
              f"flops/chip={flops:.3e} bytes/chip={bytes_acc:.3e} "
              f"coll={coll['total']:.3e}B dominant={dominant}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "zero1", "ep", "zero1_ep",
                             "scatter", "ep_scatter", "rematdots",
                             "rematdots_ep", "attnshard", "seqpar",
                             "seqpar_ep", "rematnames", "seqpar_rematnames",
                             "kv8"])
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    archs = list(configs.ARCHS) if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                key = f"{configs.ALIASES.get(arch, arch)}__{shape}__{mesh_kind}"
                if args.variant != "baseline":
                    key += f"__{args.variant}"
                path = outdir / f"{key}.json"
                if path.exists():
                    print(f"[skip existing] {key}")
                    continue
                try:
                    rec = run_one(arch, shape, mesh_kind,
                                  variant=args.variant)
                except Exception as e:  # noqa: BLE001 — record and continue
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "ok": False, "error": repr(e)}
                    failures.append(key)
                path.write_text(json.dumps(rec, indent=1))
    if failures:
        print("FAILURES:", failures)
        sys.exit(1)
    print("all dry-runs OK")


if __name__ == "__main__":
    main()
