"""One-pass Pallas TPU kernels for G_l = X_l' phi(X_l v_l), per node.

Every product of the streaming fit has this form: the round's gradient
(phi(u)_i = L_h'(y_i u_i) y_i / n), the KKT check's gradient at beta_bar
(the same map) and the power iteration (phi(u) = u / n).  A row's margin
needs only that row, so while a tile of rows sits in VMEM it yields its
margins, its weights and its share of X'w: X is read from HBM once per
product, where XLA's pair of fusions reads it twice.

Two orientations, one per layout of a node block (n, p), chosen by
``rows_on_lanes(n, p)`` from the shape alone.  The TPU lays a buffer out
with the dimension that pads least to the 128 lanes as its minor one; of a
block's two, that is n where n pads less than p, and the kernel of that
orientation reads the block in place.  Where X lies node-minor (the
layout the compiler plans for a peer network's (1024, 200, 20001), whose
1,024 nodes and 200 rows pad nothing), neither orientation reads it in
place, and XLA relayouts X ahead of the program's loops; the row
orientation's layout is then the smaller wherever p pads less than n.

- ``xpass`` (``decsvm_xpass``) reads X through its transposed view X_l'
  (p, n), with the rows of X on the lanes.  That is how a tall block whose
  n pads less than its p is stored (epsilon's (10, 40000, 2001) is stored
  n-minor), so the view is a bitcast there.  Every vector is lane-dense:
  the margins u of 128 rows are a sublane reduction of a (p, 128) slab
  against v broadcast along the lanes, the labels and weights are lane
  rows, and X'w accumulates as (p, 128) lane partials that are reduced
  across lanes once per node.  Grid (node, row tile); a block spans all p
  columns (p=2001 needs no padding: a block dimension may equal the
  array's).
- ``xpass_rows`` (``decsvm_xpass_rows``) reads X as it is, rows on the
  sublanes: the layout of a short, wide block whose p pads less (a peer
  network's (200, 20001) node block).  The margins of 8 rows are a lane
  reduction of an (8, p) slab against v, the weights an (8, 1) column, and
  X'w accumulates as (8, p) sublane partials, reduced once per node.  Grid
  (node, row tile) of 8-row groups; a block spans all p, so VMEM bounds p
  only at about 400,000 columns.

In both, a last tile past n reads rows that are masked to exactly zero,
and both products are fp32 multiply-adds on the VPU: the margins and the
gradient are as accurate as the HIGHEST-precision dots they replace (an
MXU pass at default precision rounds X to bf16; at HIGHEST a single-column
dot is compute-bound).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import losses
from repro.kernels.csvm_update import _resolve_interpret, _rup

NAME = "decsvm_xpass"
ROWS_NAME = "decsvm_xpass_rows"
WEIGHTS = ("loss", "linear")
_LANES = 128
_SUBLANES = 8
_MAX_TILE = 2048             # rows a step: the body unrolls tile / 128
_BLOCK_BYTES = 8 * 2**20     # one buffer of X's (p, tile) block
_VMEM_BUDGET = 100 * 2**20   # of the v5e core's 128 MiB


def tile_rows(p: int) -> int:
    """Rows of X a step: the most lanes (a multiple of 128, at most
    ``_MAX_TILE``) whose (p, tile) fp32 block fits ``_BLOCK_BYTES``, at
    least 128."""
    fit = _BLOCK_BYTES // (4 * _rup(p, 8)) // _LANES * _LANES
    return min(_MAX_TILE, max(_LANES, fit))


def vmem_bytes(p: int, tile: int) -> int:
    """VMEM of one launch: X's block double-buffered, the two (P, 128)
    scratch slabs and about three (p, 128) temporaries of a lane chunk."""
    return 4 * (2 * _rup(p, 8) * tile + 2 * _rup(p, _LANES) * _LANES
                + 3 * _rup(p, 8) * _LANES)


def rows_on_lanes(n: int, p: int) -> bool:
    """The orientation of a node block (n, p): True where n pads less than
    p to a multiple of the 128 lanes, so the TPU stores the block n-minor
    and ``xpass`` reads it in place; False where ``xpass_rows`` does."""
    return _rup(n, _LANES) * p < _rup(p, _LANES) * n


def supported(n: int, p: int) -> bool:
    """True when a launch of the orientation of an (n, p) block fits the
    VMEM budget."""
    if rows_on_lanes(n, p):
        return vmem_bytes(p, tile_rows(p)) <= _VMEM_BUDGET
    return rows_vmem_bytes(p, row_tile(n, p)) <= _VMEM_BUDGET


def row_tile(n: int, p: int) -> int:
    """Rows of X a step of ``xpass_rows``: the most 8-row groups whose
    (tile, p) fp32 block fits ``_BLOCK_BYTES``, at least 8, and no more
    than n rounded up to 8."""
    fit = _BLOCK_BYTES // (4 * _rup(p, _LANES)) // _SUBLANES * _SUBLANES
    return min(_rup(n, _SUBLANES), max(_SUBLANES, fit))


def rows_vmem_bytes(p: int, tile: int) -> int:
    """VMEM of one ``xpass_rows`` launch: X's block double-buffered, v's
    block double-buffered, the (8, p) partials of X'w and about three
    (8, p) temporaries of a row group."""
    return 4 * _rup(p, _LANES) * (2 * tile + 2 * _SUBLANES + 4 * _SUBLANES)


def _xpass_kernel(xt_ref, y_ref, v_ref, g_ref, vb_ref, acc_ref, *, n: int,
                  tile: int, weight: str, h: float, kernel: str):
    """One (node, row tile) step.  xt (p, tile) of X'; y (1, tile); v
    (1, P) zero-padded to P = rup(p, 128); scratch vb (P, 128) holds v
    broadcast along the lanes, acc (P, 128) the lane partials of X'w."""
    i = pl.program_id(1)
    last = pl.num_programs(1) - 1
    p = xt_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        rows = v_ref.shape[1]
        vb_ref[...] = jnp.transpose(jnp.broadcast_to(v_ref[...],
                                                     (_LANES, rows)))
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def lanes(c: int, valid: int):
        """128 rows of X from lane offset c*128 of the tile; ``valid`` < 128
        masks the rows past n to zero (select, so stale VMEM is dropped)."""
        sl = pl.ds(c * _LANES, _LANES)
        x = xt_ref[:, sl]                                     # (p, 128)
        y = y_ref[:, sl]                                      # (1, 128)
        if valid < _LANES:
            keep = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) < valid
            x = jnp.where(keep, x, 0.0)
            y = jnp.where(keep, y, 0.0)
        u = jnp.sum(x * vb_ref[:p, :], axis=0, keepdims=True)  # (1, 128)
        if weight == "loss":
            w = losses.get_kernel(kernel).dloss(y * u, h) * y
        else:
            w = u
        acc_ref[:p, :] += x * w

    full, rest = divmod(n - (pl.cdiv(n, tile) - 1) * tile, _LANES)

    @pl.when(i < last)
    def _body():
        for c in range(tile // _LANES):
            lanes(c, _LANES)

    @pl.when(i == last)
    def _tail():
        for c in range(full):
            lanes(c, _LANES)
        if rest:
            lanes(full, rest)
        g = jnp.sum(jnp.transpose(acc_ref[...]), axis=0, keepdims=True)
        g_ref[...] = g[:, :p] / n


@functools.partial(jax.jit, static_argnames=("weight", "h", "kernel",
                                             "tile", "interpret"))
def xpass(X, y, V, *, weight: str, h: float = 0.0,
          kernel: str = "epanechnikov", tile: int | None = None,
          interpret: bool | None = None):
    """G = X' phi(X V) per node, in one read of X.

    X (m, n, p) f32; y (m, n) labels (read by the ``"loss"`` map only);
    V (m, p) f32.  ``weight`` is ``"loss"`` (phi(u)_i = L_h'(y_i u_i) y_i
    / n, the smoothed-hinge gradient of ``kernel`` at bandwidth h) or
    ``"linear"`` (phi(u) = u / n).  Returns G (m, p) f32.  ``tile`` rows of
    X a step (a multiple of 128, ``tile_rows(p)`` by default; a shorter n
    takes one tile).
    """
    if weight not in WEIGHTS:
        raise ValueError(f"weight {weight!r} not in {WEIGHTS}")
    m, n, p = X.shape
    tile = tile_rows(p) if tile is None else tile
    if tile % _LANES:
        raise ValueError(f"tile {tile} is not a multiple of {_LANES}")
    interpret = _resolve_interpret(interpret)
    tile = min(tile, _rup(n, _LANES))
    P = _rup(p, _LANES)
    f32 = jnp.float32
    xt = jnp.swapaxes(X.astype(f32), 1, 2)                    # (m, p, n)
    y3 = y.astype(f32)[:, None, :]
    v3 = jnp.pad(V.astype(f32), ((0, 0), (0, P - p)))[:, None, :]
    out = pl.pallas_call(
        functools.partial(_xpass_kernel, n=n, tile=tile, weight=weight,
                          h=h, kernel=kernel),
        grid=(m, pl.cdiv(n, tile)),
        in_specs=[pl.BlockSpec((None, p, tile), lambda l, i: (l, 0, i)),
                  pl.BlockSpec((None, 1, tile), lambda l, i: (l, 0, i)),
                  pl.BlockSpec((None, 1, P), lambda l, i: (l, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, p), lambda l, i: (l, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1, p), f32),
        scratch_shapes=[pltpu.VMEM((P, _LANES), f32),
                        pltpu.VMEM((P, _LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_rup(vmem_bytes(p, tile) + 2**22, 2**20)),
        interpret=interpret,
        name=NAME,
    )(xt, y3, v3)
    return out[:, 0, :]


def _xpass_rows_kernel(x_ref, y_ref, v_ref, g_ref, acc_ref, *, n: int,
                       tile: int, weight: str, h: float, kernel: str):
    """One (node, row tile) step.  x (tile, p) of X; y (tile, 1); v (1, p);
    scratch acc (8, p) holds the sublane partials of X'w."""
    i = pl.program_id(1)
    last = pl.num_programs(1) - 1

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def group(r, valid: int):
        """8 rows of X from row r*8 of the tile; ``valid`` < 8 masks the
        rows past n to zero (select, so stale VMEM is dropped)."""
        sl = pl.ds(pl.multiple_of(r * _SUBLANES, _SUBLANES), _SUBLANES)
        x = x_ref[sl, :]                                      # (8, p)
        y = y_ref[sl, :]                                      # (8, 1)
        if valid < _SUBLANES:
            keep = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, 1),
                                            0) < valid
            x = jnp.where(keep, x, 0.0)
            y = jnp.where(keep, y, 0.0)
        u = jnp.sum(x * v_ref[...], axis=1, keepdims=True)    # (8, 1)
        if weight == "loss":
            w = losses.get_kernel(kernel).dloss(y * u, h) * y
        else:
            w = u
        acc_ref[...] += x * w

    def groups(count: int):
        if count:
            jax.lax.fori_loop(0, count, lambda r, c: (group(r, _SUBLANES),
                                                      c)[1], 0)

    full, rest = divmod(n - (pl.cdiv(n, tile) - 1) * tile, _SUBLANES)

    @pl.when(i < last)
    def _body():
        groups(tile // _SUBLANES)

    @pl.when(i == last)
    def _tail():
        groups(full)
        if rest:
            group(full, rest)
        g_ref[...] = jnp.sum(acc_ref[...], axis=0, keepdims=True) / n


@functools.partial(jax.jit, static_argnames=("weight", "h", "kernel",
                                             "tile", "interpret"))
def xpass_rows(X, y, V, *, weight: str, h: float = 0.0,
               kernel: str = "epanechnikov", tile: int | None = None,
               interpret: bool | None = None):
    """G = X' phi(X V) per node, in one read of X as it is stored, rows on
    the sublanes.  Arguments and result as ``xpass``; ``tile`` rows of X a
    step (a multiple of 8, ``row_tile(n, p)`` by default)."""
    if weight not in WEIGHTS:
        raise ValueError(f"weight {weight!r} not in {WEIGHTS}")
    m, n, p = X.shape
    tile = row_tile(n, p) if tile is None else tile
    if tile % _SUBLANES:
        raise ValueError(f"tile {tile} is not a multiple of {_SUBLANES}")
    interpret = _resolve_interpret(interpret)
    tile = min(tile, _rup(n, _SUBLANES))
    f32 = jnp.float32
    out = pl.pallas_call(
        functools.partial(_xpass_rows_kernel, n=n, tile=tile, weight=weight,
                          h=h, kernel=kernel),
        grid=(m, pl.cdiv(n, tile)),
        in_specs=[pl.BlockSpec((None, tile, p), lambda l, i: (l, i, 0)),
                  pl.BlockSpec((None, tile, 1), lambda l, i: (l, i, 0)),
                  pl.BlockSpec((None, 1, p), lambda l, i: (l, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, p), lambda l, i: (l, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1, p), f32),
        scratch_shapes=[pltpu.VMEM((_SUBLANES, p), f32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_rup(rows_vmem_bytes(p, tile) + 2**22, 2**20)),
        interpret=interpret,
        name=ROWS_NAME,
    )(X.astype(f32), y.astype(f32)[:, :, None], V.astype(f32)[:, None, :])
    return out[:, 0, :]
