"""Pallas TPU kernels: fused blockwise NT-Xent logsumexp (flash-style).

At pod-scale global batches the NT-Xent hot spot is the (anchors x
candidates) similarity matrix: XLA materializes it in HBM twice (forward
logits + backward softmax), making the loss HBM-bandwidth-bound. These
kernels never materialize it: similarity tiles are computed on the MXU from
VMEM-resident embedding blocks and immediately folded into a running
(online-softmax) logsumexp — the same trick flash attention uses for the
attention matrix, applied to the contrastive candidate axis (SURVEY §7.8).

The core op is RECTANGULAR: anchors (Ma, d) against candidates (Mc, d) with
a per-anchor ``self_idx`` column masked out. That covers both:
  * the single-device / local-negatives case — candidates == anchors,
    ``self_idx = arange`` (:func:`ntxent_loss_fused`);
  * the sharded global-negatives case — local anchors against the
    all-gathered global candidate set inside ``shard_map``
    (:func:`ntxent_loss_fused_sharded`), where gradients w.r.t. the gathered
    candidates flow back through the gather's transpose (a psum-scatter) to
    the owning shards automatically.

Structure:
  * forward — grid (row_tiles, col_tiles), col innermost; per row-tile
    scratch holds the running max/sum; one (Ma, 1) logsumexp vector out.
  * backward — softmax tiles are recomputed from the saved logsumexp and
    folded straight into two gradient contractions (anchor rows; candidate
    rows), each its own kernel accumulating into its output block. Peak
    memory stays O((Ma + Mc)·d + tile²).
  * both dims are padded to hardware-aligned tiles; padded candidates are
    masked to -inf, padded anchors are neutralized by zero cotangents.

Compiled by Mosaic on TPU. ``interpret=True`` is used on the CPU backend
only, which is how the CPU tests run the kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from simclr_tpu.ops.ntxent import _l2_normalize, gather_global_candidates

_NEG_INF = -1e9


def _interpret() -> bool:
    # the Pallas interpreter is for the CPU backend only (tests); every other
    # backend compiles the kernel or fails
    return jax.default_backend() == "cpu"


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _tile_and_pad(m: int) -> tuple[int, int]:
    """(tile, padded_m): hardware-aligned tiling for any batch size.

    Rows are padded up to the tile so block shapes never fall below the TPU
    (8, 128) native tile; padded candidate columns are masked to -inf inside
    the kernels (flash-kernel style), so results are exact for the real m.
    """
    if m >= 128:
        tile = 128
    else:
        tile = -(-m // 8) * 8  # next multiple of 8: one tile covers everything
    return tile, -(-m // tile) * tile


def _pad_rows(x: jnp.ndarray, m_pad: int, fill=0) -> jnp.ndarray:
    m = x.shape[0]
    if m == m_pad:
        return x
    pad_widths = [(0, m_pad - m)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad_widths, constant_values=fill)


# ---------------------------------------------------------------------------
# forward: per-anchor logsumexp of  A @ C.T / tau  with self columns masked
# ---------------------------------------------------------------------------

def _lse_kernel(
    self_ref, a_ref, c_ref, lse_ref, m_scr, s_scr, *, inv_temp, ta, tc, mc_real
):
    j = pl.program_id(1)

    sim = (
        jnp.dot(a_ref[:], c_ref[:].T, preferred_element_type=jnp.float32)
        * inv_temp
    )
    cols = jax.lax.broadcasted_iota(jnp.int32, (ta, tc), 1) + j * tc
    # mask each anchor's own column and the padded candidate tail
    sim = jnp.where((cols == self_ref[:]) | (cols >= mc_real), _NEG_INF, sim)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full((ta, 1), _NEG_INF, jnp.float32)
        s_scr[:] = jnp.zeros((ta, 1), jnp.float32)

    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, sim.max(axis=1, keepdims=True))
    s_scr[:] = s_scr[:] * jnp.exp(m_prev - m_new) + jnp.exp(sim - m_new).sum(
        axis=1, keepdims=True
    )
    m_scr[:] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        lse_ref[:] = jnp.log(s_scr[:]) + m_scr[:]


def _lse_fwd_impl(anchors, candidates, self_idx, temperature):
    ma, d = anchors.shape
    mc = candidates.shape[0]
    ta, ma_pad = _tile_and_pad(ma)
    tc, mc_pad = _tile_and_pad(mc)
    ap = _pad_rows(anchors, ma_pad)
    cp = _pad_rows(candidates, mc_pad)
    sp = _pad_rows(self_idx.astype(jnp.int32).reshape(ma, 1), ma_pad, fill=-1)

    kernel = functools.partial(
        _lse_kernel, inv_temp=1.0 / temperature, ta=ta, tc=tc, mc_real=mc
    )
    lse = pl.pallas_call(
        kernel,
        grid=(ma_pad // ta, mc_pad // tc),
        in_specs=[
            pl.BlockSpec((ta, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((ta, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tc, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((ta, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ma_pad, 1), jnp.float32),
        scratch_shapes=[_vmem((ta, 1)), _vmem((ta, 1))],
        interpret=_interpret(),
    )(sp, ap, cp)
    return lse[:ma, 0]


# ---------------------------------------------------------------------------
# backward: dA_i = sum_j g_i P_ij C_j / tau ;  dC_j = sum_i g_i P_ij A_i / tau
# with P_ij = exp(sim_ij - lse_i), recomputed tile-by-tile
# ---------------------------------------------------------------------------

def _danchor_kernel(
    self_ref, a_ref, c_ref, lse_ref, g_ref, acc_ref, *, inv_temp, ta, tc, mc_real
):
    """Output tile: anchor rows; reduction over candidate tiles (inner)."""
    k = pl.program_id(1)
    sim = (
        jnp.dot(a_ref[:], c_ref[:].T, preferred_element_type=jnp.float32)
        * inv_temp
    )
    cols = jax.lax.broadcasted_iota(jnp.int32, (ta, tc), 1) + k * tc
    sim = jnp.where((cols == self_ref[:]) | (cols >= mc_real), _NEG_INF, sim)
    w = jnp.exp(sim - lse_ref[:]) * g_ref[:]  # lse/g broadcast over columns

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(w, c_ref[:], preferred_element_type=jnp.float32)


def _dcandidate_kernel(
    self_ref, c_ref, a_ref, lse_ref, g_ref, acc_ref, *, inv_temp, tc, ta, mc_real
):
    """Output tile: candidate rows; reduction over anchor tiles (inner).

    ``self_ref``/``lse_ref``/``g_ref`` are blocks of the ANCHOR (reduction)
    axis; the self-mask triggers where the candidate row equals the anchor's
    self column.
    """
    o = pl.program_id(0)
    sim = (
        jnp.dot(c_ref[:], a_ref[:].T, preferred_element_type=jnp.float32)
        * inv_temp
    )  # (tc, ta): rows = candidates, cols = anchors
    rows = jax.lax.broadcasted_iota(jnp.int32, (tc, ta), 0) + o * tc
    sim = jnp.where(
        (rows == self_ref[:].reshape(1, ta)) | (rows >= mc_real), _NEG_INF, sim
    )
    w = jnp.exp(sim - lse_ref[:].reshape(1, ta)) * g_ref[:].reshape(1, ta)

    @pl.when(pl.program_id(1) == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(w, a_ref[:], preferred_element_type=jnp.float32)


def _lse_bwd_impl(anchors, candidates, self_idx, lse, g, temperature):
    ma, d = anchors.shape
    mc = candidates.shape[0]
    ta, ma_pad = _tile_and_pad(ma)
    tc, mc_pad = _tile_and_pad(mc)
    ap = _pad_rows(anchors, ma_pad)
    cp = _pad_rows(candidates, mc_pad)
    sp = _pad_rows(self_idx.astype(jnp.int32).reshape(ma, 1), ma_pad, fill=-1)
    lp = _pad_rows(lse.reshape(ma, 1), ma_pad)          # pad 0: finite
    gp = _pad_rows(g.astype(jnp.float32).reshape(ma, 1), ma_pad)  # pad 0: inert

    da = pl.pallas_call(
        functools.partial(
            _danchor_kernel, inv_temp=1.0 / temperature, ta=ta, tc=tc, mc_real=mc
        ),
        grid=(ma_pad // ta, mc_pad // tc),
        in_specs=[
            pl.BlockSpec((ta, 1), lambda o, k: (o, 0)),
            pl.BlockSpec((ta, d), lambda o, k: (o, 0)),
            pl.BlockSpec((tc, d), lambda o, k: (k, 0)),
            pl.BlockSpec((ta, 1), lambda o, k: (o, 0)),
            pl.BlockSpec((ta, 1), lambda o, k: (o, 0)),
        ],
        out_specs=pl.BlockSpec((ta, d), lambda o, k: (o, 0)),
        out_shape=jax.ShapeDtypeStruct((ma_pad, d), jnp.float32),
        interpret=_interpret(),
    )(sp, ap, cp, lp, gp)

    dc = pl.pallas_call(
        functools.partial(
            _dcandidate_kernel, inv_temp=1.0 / temperature, tc=tc, ta=ta, mc_real=mc
        ),
        grid=(mc_pad // tc, ma_pad // ta),
        in_specs=[
            pl.BlockSpec((ta, 1), lambda o, k: (k, 0)),
            pl.BlockSpec((tc, d), lambda o, k: (o, 0)),
            pl.BlockSpec((ta, d), lambda o, k: (k, 0)),
            pl.BlockSpec((ta, 1), lambda o, k: (k, 0)),
            pl.BlockSpec((ta, 1), lambda o, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((tc, d), lambda o, k: (o, 0)),
        out_shape=jax.ShapeDtypeStruct((mc_pad, d), jnp.float32),
        interpret=_interpret(),
    )(sp, cp, ap, lp, gp)

    inv_t = 1.0 / temperature
    return da[:ma] * inv_t, dc[:mc] * inv_t


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def masked_lse_pair(anchors, candidates, self_idx, temperature):
    """Per-anchor logsumexp of ``anchors @ candidates.T / temperature`` with
    column ``self_idx[i]`` masked for anchor ``i``. Shape (Ma,)."""
    return _lse_fwd_impl(anchors, candidates, self_idx, temperature)


def _pair_fwd(anchors, candidates, self_idx, temperature):
    lse = _lse_fwd_impl(anchors, candidates, self_idx, temperature)
    return lse, (anchors, candidates, self_idx, lse)


def _pair_bwd(temperature, res, g):
    anchors, candidates, self_idx, lse = res
    da, dc = _lse_bwd_impl(anchors, candidates, self_idx, lse, g, temperature)
    dself = np.zeros(self_idx.shape, dtype=jax.dtypes.float0)
    return da, dc, dself


masked_lse_pair.defvjp(_pair_fwd, _pair_bwd)


# ---------------------------------------------------------------------------
# public losses
# ---------------------------------------------------------------------------

def ntxent_loss_fused(
    z0: jnp.ndarray, z1: jnp.ndarray, temperature: float = 0.5
) -> jnp.ndarray:
    """Fused-kernel NT-Xent, numerically equal to ``ntxent.ntxent_loss``
    (mean reduction). Candidates are the anchors themselves.

    Normalization and the positive term run in plain JAX (cheap, autodiffed);
    the quadratic masked-logsumexp runs in the Pallas kernel with a custom
    VJP that recomputes softmax tiles instead of storing the matrix.
    """
    if z0.shape != z1.shape:
        raise ValueError(
            f"view embeddings must have identical shapes, got {z0.shape} vs {z1.shape}"
        )
    n = z0.shape[0]
    z = _l2_normalize(jnp.concatenate([z0, z1], axis=0))
    lse = masked_lse_pair(z, z, jnp.arange(2 * n, dtype=jnp.int32), float(temperature))
    pos = jnp.sum(z * jnp.roll(z, n, axis=0), axis=-1) / temperature
    return (lse - pos).mean()


def ntxent_loss_fused_sharded(
    z0: jnp.ndarray,
    z1: jnp.ndarray,
    axis_name: str,
    temperature: float = 0.5,
) -> jnp.ndarray:
    """Global-negatives NT-Xent with the fused kernel, inside ``shard_map``.

    Same objective and candidate layout as
    ``ntxent.ntxent_loss_sharded_rows`` (all-gathered ``[all z0 | all z1]``
    candidates, local anchor rows), but the (2B_local x 2B_global)
    similarity block lives only in VMEM tiles. Gradients w.r.t. the gathered
    candidates flow back through the gather transpose (psum-scatter) to the
    owning shards.
    """
    z_local, candidates, self_idx, _pos_idx = gather_global_candidates(
        z0, z1, axis_name
    )
    lse = masked_lse_pair(z_local, candidates, self_idx, float(temperature))
    # positives are co-resident (z0_i and z1_i on the same shard): cheap
    # local row-wise dot instead of indexing the gathered set by _pos_idx
    n_local = z0.shape[0]
    pos = jnp.sum(z_local * jnp.roll(z_local, n_local, axis=0), axis=-1) / temperature
    return jax.lax.pmean((lse - pos).mean(), axis_name)
