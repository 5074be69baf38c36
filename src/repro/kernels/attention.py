"""Fused attention — Pallas TPU kernels, forward and backward.

Softmax attention over ``(B, S, heads * width)`` rows, with the scores
kept on chip: each grid step holds one ``(block, block)`` score tile per
head in VMEM, folds it into a running max and sum (online softmax), and
no ``(B, S, H, S)`` tensor reaches HBM in the forward or the backward.
The backward is the usual flash form: from the saved per-row
log-sum-exp and ``rowsum(dO * O)`` a dK/dV kernel (kv blocks outer, q
blocks inner) and a dQ kernel (q blocks outer, kv blocks inner) rebuild
each probability tile where they need it.

Each grid step takes one row and every head of it, so the operands stay
in the layout the projections give them (no transposes) and the step
count is ``B * blocks^2``, not ``B * H * blocks^2``. Rows shorter than
a block (queries) are packed several to a block, each kept to its own
keys by the mask, so that they fill the tiles and take fewer steps.
Keys and values may have fewer heads than the queries (GQA: query head
``h`` reads kv head ``h // (H / KV)``), and the query/key width may
differ from the value width (MLA).

Only blocks inside a row's extent (last real position + 1,
``sparton.row_extents``) run. The extents are scalar-prefetched; a q
block past the extent writes zeros, and a kv block past it, or above
the diagonal of a causal row, is neither computed (``pl.when``) nor
fetched: its index map clamps to the block the pipeline already holds,
as the head's ``last_live_tile`` does. Inside a live block the key mask
and the causal order apply element by element, so any mask is exact,
not only right padding. Pad positions feed only pad positions, so no
real position's output or gradient depends on what the skipped blocks
would have held.

Precision: bf16 (the inputs') q/k/v operands with f32 accumulation; f32
scores, running max and sum; probabilities, and in the backward the
score gradient, rounded to the operand dtype only as MXU operands. The
softmax scale is applied to q in f32 before that rounding, as
``models/attention.chunked_attention`` does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._common import NEG_INF, compiler_params, pad_to
from repro.kernels.sparton import row_extents

# q and kv block length: a sequence of at most BLOCK positions is one
# block of its own length, a longer one is padded to a multiple
BLOCK = 128

_TRANS_B = (((1,), (1,)), ((), ()))


def _block_len(seq_len: int) -> int:
    """The q and kv block length at a (packed) sequence length."""
    return seq_len if seq_len <= BLOCK else BLOCK


def attention_live_block_share(extents, seq_len: int, block_q: int,
                               block_k: int, causal: bool) -> float:
    """Share of the ``(row, q block, kv block)`` triples that the
    kernels run for rows of these extents: q blocks before the extent,
    kv blocks before it and, for causal rows, at or below the
    diagonal."""
    ext = np.asarray(extents, np.int64)
    n_q, n_k = -(-seq_len // block_q), -(-seq_len // block_k)
    live_q = -(-ext // block_q)
    live_k = -(-ext // block_k)
    qi = np.arange(n_q)
    # kv blocks q block qi visits: those starting at or before its end
    reach = np.minimum(((qi + 1) * block_q - 1) // block_k + 1, n_k)
    per_q = (np.minimum(live_k[:, None], reach[None]) if causal
             else np.broadcast_to(live_k[:, None], (ext.size, n_q)))
    run = np.where(qi[None] < live_q[:, None], per_q, 0)
    return float(run.sum()) / (ext.size * n_q * n_k)


# ---------------------------------------------------------------------------
# block maps: which blocks run, and which block each step fetches
# ---------------------------------------------------------------------------

def _live(ext_ref, b, block):
    """Blocks of row ``b`` that reach a real position."""
    return (ext_ref[b] + block - 1) // block


def _kv_reach(n_live, qi, causal):
    """kv blocks q block ``qi`` visits (q and kv blocks share a length,
    so a causal q block sees the kv blocks up to its own index)."""
    return jnp.minimum(n_live, qi + 1) if causal else n_live


def _q_first(ki, causal):
    """First q block that kv block ``ki`` is visited by."""
    return ki if causal else 0


def _last(n):
    return jnp.maximum(n - 1, 0)


def _qk_maps(block, causal):
    """Index maps of the q-outer grid ``(b, qi, ki)``: the q side's
    block and the kv side's. A step that does not run fetches what the
    step before it fetched."""
    def q_side(b, qi, ki, ext):
        return (b, jnp.minimum(qi, _last(_live(ext, b, block))), 0)

    def kv_side(b, qi, ki, ext):
        n = _live(ext, b, block)
        q = jnp.minimum(qi, _last(n))
        last = _last(_kv_reach(n, q, causal))
        return (b, jnp.where(qi < n, jnp.minimum(ki, last), last), 0)

    return q_side, kv_side


def _kq_maps(block, causal):
    """Index maps of the kv-outer grid ``(b, ki, qi)`` of the dK/dV
    kernel, in the same way."""
    def kv_side(b, ki, qi, ext):
        return (b, jnp.minimum(ki, _last(_live(ext, b, block))), 0)

    def q_side(b, ki, qi, ext):
        n = _live(ext, b, block)
        first = _q_first(jnp.minimum(ki, _last(n)), causal)
        inside = jnp.clip(qi, first, _last(n))
        return (b, jnp.where(ki < n, inside, _last(n)), 0)

    return kv_side, q_side


def _mask_side(kv_side):
    """The key mask's ``(B, 1, Sp)`` block map, from the kv side's."""
    def index(*grid):
        b, kv, _ = kv_side(*grid)
        return (b, 0, kv)
    return index


def _keep(mask_ref, qi, ki, block, causal, segment):
    """The tile's keep mask: the key mask, the causal order and, for
    packed rows, the row each position belongs to."""
    keep = mask_ref[...] > 0                         # (1, bk)
    if causal or segment:
        shape = (block, block)
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + qi * block
        cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + ki * block
        if causal:
            keep = keep & (cols <= rows)
        if segment:
            keep = keep & (rows // segment == cols // segment)
    return keep


def _head_scores(q, k, h, g, dqk, scale, keep):
    """Head ``h``'s masked f32 score tile, and its q and k operands."""
    qh = (q[:, h * dqk:(h + 1) * dqk].astype(jnp.float32)
          * scale).astype(k.dtype)
    kh = k[:, g * dqk:(g + 1) * dqk]
    s = jax.lax.dot_general(qh, kh, _TRANS_B,
                            preferred_element_type=jnp.float32)
    return jnp.where(keep, s, NEG_INF), qh, kh


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(ext_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, *rest,
                heads, group, dqk, dv, scale, causal, segment, block,
                n_blocks, save_lse):
    if save_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    b, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n = _live(ext_ref, b, block)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when((qi < n) & (ki < _kv_reach(n, qi, causal)))
    def _block():
        keep = _keep(mask_ref, qi, ki, block, causal, segment)
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        for h in range(heads):
            g = h // group
            s, _, _ = _head_scores(q, k, h, g, dqk, scale, keep)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = alpha * acc_scr[h] + jnp.dot(
                p.astype(v.dtype), v[:, g * dv:(g + 1) * dv],
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    # a q block past the extent leaves its scratch as _init set it: its
    # output is 0
    @pl.when(ki == n_blocks - 1)
    def _store():
        sums = [jnp.maximum(l_scr[h], 1e-30) for h in range(heads)]
        o_ref[...] = jnp.concatenate(
            [acc_scr[h] / sums[h] for h in range(heads)],
            axis=1).astype(o_ref.dtype)
        if save_lse:
            lse_ref[...] = jnp.concatenate(
                [m_scr[h] + jnp.log(sums[h]) for h in range(heads)], axis=1)


def _forward(q, k, v, mask_t, ext, *, heads, kv_heads, dqk, dv, causal,
             segment, block, save_lse, interpret):
    B, Sp, _ = q.shape
    n_blocks = Sp // block
    q_side, kv_side = _qk_maps(block, causal)
    out_shape = [jax.ShapeDtypeStruct((B, Sp, heads * dv), q.dtype)]
    out_specs = [pl.BlockSpec((None, block, heads * dv),
                              lambda b, i, j, e: (b, i, 0))]
    if save_lse:
        out_shape.append(jax.ShapeDtypeStruct((B, Sp, heads), jnp.float32))
        out_specs.append(pl.BlockSpec((None, block, heads),
                                      lambda b, i, j, e: (b, i, 0)))
    kernel = functools.partial(
        _fwd_kernel, heads=heads, group=heads // kv_heads, dqk=dqk, dv=dv,
        scale=dqk ** -0.5, causal=causal, segment=segment, block=block,
        n_blocks=n_blocks, save_lse=save_lse)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_blocks, n_blocks),
            in_specs=[
                pl.BlockSpec((None, block, heads * dqk), q_side),
                pl.BlockSpec((None, block, kv_heads * dqk), kv_side),
                pl.BlockSpec((None, block, kv_heads * dv), kv_side),
                pl.BlockSpec((None, 1, block), _mask_side(kv_side)),
            ],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((heads, block, 1), jnp.float32),
                pltpu.VMEM((heads, block, 1), jnp.float32),
                pltpu.VMEM((heads, block, dv), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=compiler_params("parallel", "parallel",
                                        "arbitrary"),
        interpret=interpret,
        name="attention_fwd",
    )(ext, q, k, v, mask_t)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _column(tile, h):
    """Column ``h`` of a ``(block, heads)`` f32 tile, as ``(block, 1)``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.sum(jnp.where(lane == h, tile, 0.0), axis=1, keepdims=True)


def _head_grads(q, k, v, do, lse, di, keep, h, g, dqk, dv, scale):
    """Head ``h``'s probability tile and score gradient, with the
    operands the gradient matmuls take."""
    s, qh, kh = _head_scores(q, k, h, g, dqk, scale, keep)
    p = jnp.exp(s - _column(lse, h))
    doh = do[:, h * dv:(h + 1) * dv]
    dp = jax.lax.dot_general(doh, v[:, g * dv:(g + 1) * dv], _TRANS_B,
                             preferred_element_type=jnp.float32)
    ds = p * (dp - _column(di, h))
    return p, ds, qh, kh, doh


def _dq_kernel(ext_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
               mask_ref, dq_ref, acc_scr, *, heads, group, dqk, dv, scale,
               causal, segment, block, n_blocks):
    b, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n = _live(ext_ref, b, block)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when((qi < n) & (ki < _kv_reach(n, qi, causal)))
    def _block():
        keep = _keep(mask_ref, qi, ki, block, causal, segment)
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        lse, di = lse_ref[...], di_ref[...]
        for h in range(heads):
            _, ds, _, kh, _ = _head_grads(q, k, v, do, lse, di, keep, h,
                                          h // group, dqk, dv, scale)
            acc_scr[h] += jnp.dot(ds.astype(kh.dtype), kh,
                                  preferred_element_type=jnp.float32)

    @pl.when(ki == n_blocks - 1)
    def _store():
        dq_ref[...] = jnp.concatenate(
            [acc_scr[h] * scale for h in range(heads)],
            axis=1).astype(dq_ref.dtype)


def _dkv_kernel(ext_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                mask_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, heads, group,
                dqk, dv, scale, causal, segment, block, n_blocks):
    b, ki, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n = _live(ext_ref, b, block)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    @pl.when((ki < n) & (qi < n) & (qi >= _q_first(ki, causal)))
    def _block():
        keep = _keep(mask_ref, qi, ki, block, causal, segment)
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        lse, di = lse_ref[...], di_ref[...]
        for h in range(heads):
            g = h // group
            p, ds, qh, _, doh = _head_grads(q, k, v, do, lse, di, keep, h,
                                            g, dqk, dv, scale)
            dv_scr[g] += jnp.dot(p.T.astype(doh.dtype), doh,
                                 preferred_element_type=jnp.float32)
            dk_scr[g] += jnp.dot(ds.T.astype(qh.dtype), qh,
                                 preferred_element_type=jnp.float32)

    @pl.when(qi == n_blocks - 1)
    def _store():
        kv_heads = heads // group
        dk_ref[...] = jnp.concatenate(
            [dk_scr[g] for g in range(kv_heads)], axis=1).astype(dk_ref.dtype)
        dv_ref[...] = jnp.concatenate(
            [dv_scr[g] for g in range(kv_heads)], axis=1).astype(dv_ref.dtype)


def _backward(q, k, v, o, lse, do, mask_t, ext, *, heads, kv_heads, dqk,
              dv, causal, segment, block, interpret):
    B, Sp, _ = q.shape
    n_blocks = Sp // block
    di = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32))
                 .reshape(B, Sp, heads, dv), axis=-1)
    static = dict(heads=heads, group=heads // kv_heads, dqk=dqk, dv=dv,
                  scale=dqk ** -0.5, causal=causal, segment=segment,
                  block=block, n_blocks=n_blocks)

    def specs(q_side, kv_side):
        return [pl.BlockSpec((None, block, heads * dqk), q_side),
                pl.BlockSpec((None, block, kv_heads * dqk), kv_side),
                pl.BlockSpec((None, block, kv_heads * dv), kv_side),
                pl.BlockSpec((None, block, heads * dv), q_side),
                pl.BlockSpec((None, block, heads), q_side),
                pl.BlockSpec((None, block, heads), q_side),
                pl.BlockSpec((None, 1, block), _mask_side(kv_side))]

    grid = (B, n_blocks, n_blocks)
    params = compiler_params("parallel", "parallel", "arbitrary")
    own_block = lambda b, i, j, e: (b, i, 0)
    args = (ext, q, k, v, do, lse, di, mask_t)

    q_side, kv_side = _qk_maps(block, causal)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=specs(q_side, kv_side),
            out_specs=pl.BlockSpec((None, block, heads * dqk), own_block),
            scratch_shapes=[pltpu.VMEM((heads, block, dqk), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=params, interpret=interpret, name="attention_dq",
    )(*args)

    kv_side, q_side = _kq_maps(block, causal)
    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=specs(q_side, kv_side),
            out_specs=[
                pl.BlockSpec((None, block, kv_heads * dqk), own_block),
                pl.BlockSpec((None, block, kv_heads * dv), own_block),
            ],
            scratch_shapes=[
                pltpu.VMEM((kv_heads, block, dqk), jnp.float32),
                pltpu.VMEM((kv_heads, block, dv), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=params, interpret=interpret, name="attention_dkv",
    )(*args)
    return dq, dk, dv_


# ---------------------------------------------------------------------------
# differentiable entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    kv_mask: jax.Array, causal: bool,
                    interpret: bool = False) -> jax.Array:
    """Softmax attention; returns ``(B, S, H, dv)`` in ``q``'s dtype.

    ``q`` is ``(B, S, H, dqk)``, ``k`` ``(B, S, KV, dqk)``, ``v``
    ``(B, S, KV, dv)`` with ``KV`` dividing ``H``; ``kv_mask`` ``(B, S)``,
    nonzero = keep. Query and key positions are ``0 .. S-1``. Outputs
    in q blocks past a row's extent are 0."""
    return _run(q, k, v, kv_mask, causal, interpret, save_lse=False)[0]


def _packing(B, S):
    """Rows packed into one sequence of the kernels: a sequence shorter
    than a block shares it with as many others as fit and divide the
    batch, so that short rows (queries) fill the block's tiles and the
    grid takes fewer steps; a mask keeps each row to its own keys."""
    r = BLOCK // S if S < BLOCK and BLOCK % S == 0 else 1
    while B % r:
        r //= 2
    return r


def _flat(x, r, block):
    """``(B, S, heads, width)`` as ``(B / r, Sp, heads * width)``: ``r``
    rows to a packed sequence, padded to a multiple of the block."""
    B, S = x.shape[:2]
    return pad_to(x.reshape(B // r, r * S, -1), 1, block)


def _unflat(x, shape):
    """``_flat``'s inverse, for a result of ``shape``."""
    B, S = shape[:2]
    return x[:, :B * S // x.shape[0]].reshape(shape)


def _run(q, k, v, kv_mask, causal, interpret, save_lse):
    """The forward on packed, flattened rows; returns the output and
    what the backward takes."""
    B, S, H, dqk = q.shape
    KV, dv = v.shape[2:]
    r = _packing(B, S)
    block = _block_len(r * S)
    qf, kf, vf = (_flat(x, r, block) for x in (q, k, v))
    mask = pad_to(kv_mask.reshape(B // r, r * S).astype(jnp.int32), 1, block)
    ext = row_extents(mask)
    outs = _forward(qf, kf, vf, mask[:, None, :], ext, heads=H, kv_heads=KV,
                    dqk=dqk, dv=dv, causal=causal,
                    segment=S if r > 1 else None, block=block,
                    save_lse=save_lse, interpret=interpret)
    return _unflat(outs[0], (B, S, H, dv)), (qf, kf, vf, mask, ext, *outs)


def _fused_fwd(q, k, v, kv_mask, causal, interpret):
    return _run(q, k, v, kv_mask, causal, interpret, save_lse=True)


def _fused_bwd(causal, interpret, res, dout):
    qf, kf, vf, mask, ext, o, lse = res
    B, S, H, dv = dout.shape
    dqk = qf.shape[2] // H
    KV = vf.shape[2] // dv
    r = B // qf.shape[0]
    block = _block_len(r * S)
    dq, dk, dv_ = _backward(
        qf, kf, vf, o, lse, _flat(dout, r, block), mask[:, None, :], ext,
        heads=H, kv_heads=KV, dqk=dqk, dv=dv, causal=causal,
        segment=S if r > 1 else None, block=block, interpret=interpret)
    return (_unflat(dq, (B, S, H, dqk)), _unflat(dk, (B, S, KV, dqk)),
            _unflat(dv_, (B, S, KV, dv)), None)


fused_attention.defvjp(_fused_fwd, _fused_bwd)
