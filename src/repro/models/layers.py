"""Shared model primitives: norms, RoPE, attention, MLP, MoE.

Attention is implemented *blockwise with online softmax* (the flash pattern)
in pure XLA so that (a) 32k/512k sequences fit memory without Pallas, and
(b) the HLO is scan-shaped and stays small for the 512-device dry-run compile.

``attention`` is the one entry the models call.  It sends causal
self-attention to a fused Pallas flash-attention kernel (the library's
splash attention, forward and backward) when the program is lowered for a TPU and the call fits the kernel:
no sliding window, no query offset, as many kv heads as query heads, equal q
and v head sizes, equal q and kv lengths that are a multiple of 128 and at
least ``FLASH_MIN_SEQ``, and a head size up to 128 or a multiple of 128.  On
a mesh the kernel runs inside ``jax.shard_map`` (GSPMD cannot split a Mosaic
kernel): each device takes its own rows, split over the data axes, and its
own heads, split over ``model``, and the call fits only where both divide.
Every other call, and every call lowered for another platform, runs
``blockwise_attention``.

Two block-enumeration modes of ``blockwise_attention``:

* rectangle (default): every (q-block, kv-block) pair is computed and masked.
  Simple, but causal masking wastes ~2x FLOPs at long sequence.
* ``pairs=True``: only blocks intersecting the causal/sliding-window band are
  enumerated (a static index list scanned with dynamic slices).  Exact-FLOPs
  attention — one of the §Perf optimizations; numerically identical.
"""

from __future__ import annotations

import collections
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.extend import core as jex_core
from jax.interpreters import ad, mlir
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


# --------------------------------------------------------------------- norms
def rms_norm(x, gamma, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * lax.rsqrt(ms + eps)).astype(dt) * gamma


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) * lax.rsqrt(var + eps)).astype(dt) * gamma + beta


# ---------------------------------------------------------------------- RoPE
def rope(x, positions, theta: float = 10000.0):
    """Rotary embedding.  x: (..., S, d); positions: (S,) or broadcastable."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (S, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rot.astype(x.dtype)


def sinusoidal_positions(seq: int, d: int):
    pos = np.arange(seq)[:, None]
    div = np.exp(np.arange(0, d, 2) / d * -math.log(10000.0))
    table = np.zeros((seq, d), np.float32)
    table[:, 0::2] = np.sin(pos * div)
    table[:, 1::2] = np.cos(pos * div)
    return jnp.asarray(table)


# ------------------------------------------------------------ mask predicate
def _block_mask(q_pos, kv_pos, *, causal: bool, window: int):
    """(qb, kvb) boolean visibility for absolute positions."""
    m = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        m &= kv_pos[None, :] <= q_pos[:, None]
    if window > 0:
        m &= kv_pos[None, :] > q_pos[:, None] - window
    return m


def band_pairs(
    nq: int, nk: int, q_block: int, kv_block: int, *,
    causal: bool, window: int, q_offset_blocks: int = 0,
) -> np.ndarray:
    """Static (qi, kj) block pairs intersecting the causal/window band."""
    pairs = []
    for qi in range(nq):
        q_lo = (qi + q_offset_blocks) * q_block
        q_hi = q_lo + q_block - 1
        for kj in range(nk):
            k_lo, k_hi = kj * kv_block, kj * kv_block + kv_block - 1
            if causal and k_lo > q_hi:
                continue
            # window left edge for the EARLIEST query in the block: the
            # block is invisible only if even that query cannot see it
            if window > 0 and k_hi <= q_lo - window:
                continue
            pairs.append((qi, kj))
    return np.asarray(pairs, np.int32).reshape(-1, 2)


# ------------------------------------------------------- blockwise attention
def blockwise_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = 512,
    kv_block: int = 512,
    pairs: bool = False,
    q_offset: int = 0,
    mask_mode: str = "where",
):
    """Online-softmax attention.  q: (B,Hq,Sq,hd); k,v: (B,Hkv,Skv,hd[v]).

    GQA is handled by folding query heads into (Hkv, G) so K/V are never
    repeated in memory.  ``q_offset`` places queries at absolute positions
    ``q_offset + arange(Sq)`` (used by chunked prefill / speculative decode).
    """
    B, Hq, Sq, hd = q.shape
    _, Hkv, Skv, hdv = v.shape
    G = Hq // Hkv
    assert Hq == G * Hkv, f"GQA heads {Hq} not a multiple of kv heads {Hkv}"
    qb = min(q_block, Sq)
    kb = min(kv_block, Skv)
    # pad ragged tails; padded KV positions are masked out below, padded Q
    # rows are sliced off the output
    Sq0, Skv0 = Sq, Skv
    if Sq % qb:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, qb - Sq % qb), (0, 0)))
        Sq = q.shape[2]
    if Skv % kb:
        pad = kb - Skv % kb
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        Skv = k.shape[2]
    nq, nk = Sq // qb, Skv // kb

    qg = q.reshape(B, Hkv, G, Sq, hd) * (hd ** -0.5)

    def block(qi_idx, kj_idx, qi, m, l, acc):
        kj = lax.dynamic_slice_in_dim(k, kj_idx * kb, kb, axis=2)
        vj = lax.dynamic_slice_in_dim(v, kj_idx * kb, kb, axis=2)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qi, kj, preferred_element_type=jnp.float32)
        q_pos = q_offset + qi_idx * qb + jnp.arange(qb)
        kv_pos = kj_idx * kb + jnp.arange(kb)
        mask = _block_mask(q_pos, kv_pos, causal=causal, window=window)
        mask &= (kv_pos < Skv0)[None, :]          # padded KV tail is invisible
        if mask_mode == "additive":
            # 2-D additive bias broadcasts inside the fusion; the `where`
            # form tempts XLA into materialising (B,H,G,qb,kvb) pred buffers
            s = s + jnp.where(mask, 0.0, _NEG_INF)[None, None, None]
        else:
            s = jnp.where(mask[None, None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        pv = jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(v.dtype), vj, preferred_element_type=jnp.float32
        )
        acc_new = acc * corr[..., None] + pv
        return m_new, l_new, acc_new

    if not pairs:

        def q_step(_, qi_idx):
            qi = lax.dynamic_slice_in_dim(qg, qi_idx * qb, qb, axis=3)
            init = (
                jnp.full((B, Hkv, G, qb), _NEG_INF, jnp.float32),
                jnp.zeros((B, Hkv, G, qb), jnp.float32),
                jnp.zeros((B, Hkv, G, qb, hdv), jnp.float32),
            )

            def kv_step(carry, kj_idx):
                return block(qi_idx, kj_idx, qi, *carry), None

            (m, l, acc), _ = lax.scan(kv_step, init, jnp.arange(nk))
            out = acc / jnp.where(l == 0, 1.0, l)[..., None]
            return None, out

        _, blocks = lax.scan(q_step, None, jnp.arange(nq))
        # blocks: (nq, B, Hkv, G, qb, hdv) -> (B, Hq, Sq, hdv)
        out = jnp.moveaxis(blocks, 0, 3).reshape(B, Hkv, G, Sq, hdv)
        return out.reshape(B, Hq, Sq, hdv)[:, :, :Sq0].astype(v.dtype)

    # ---- exact band enumeration: scan over static (qi, kj) pairs ----------
    pair_arr = jnp.asarray(
        band_pairs(nq, nk, qb, kb, causal=causal, window=window, q_offset_blocks=q_offset // qb)
    )
    m0 = jnp.full((nq, B, Hkv, G, qb), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((nq, B, Hkv, G, qb), jnp.float32)
    a0 = jnp.zeros((nq, B, Hkv, G, qb, hdv), jnp.float32)

    def pair_step(carry, pair):
        m_all, l_all, a_all = carry
        qi_idx, kj_idx = pair[0], pair[1]
        qi = lax.dynamic_slice_in_dim(qg, qi_idx * qb, qb, axis=3)
        m = lax.dynamic_index_in_dim(m_all, qi_idx, 0, keepdims=False)
        l = lax.dynamic_index_in_dim(l_all, qi_idx, 0, keepdims=False)
        acc = lax.dynamic_index_in_dim(a_all, qi_idx, 0, keepdims=False)
        m, l, acc = block(qi_idx, kj_idx, qi, m, l, acc)
        m_all = lax.dynamic_update_index_in_dim(m_all, m, qi_idx, 0)
        l_all = lax.dynamic_update_index_in_dim(l_all, l, qi_idx, 0)
        a_all = lax.dynamic_update_index_in_dim(a_all, acc, qi_idx, 0)
        return (m_all, l_all, a_all), None

    (m_all, l_all, a_all), _ = lax.scan(pair_step, (m0, l0, a0), pair_arr)
    out = a_all / jnp.where(l_all == 0, 1.0, l_all)[..., None]
    out = jnp.moveaxis(out, 0, 3).reshape(B, Hkv, G, Sq, hdv)
    return out.reshape(B, Hq, Sq, hdv)[:, :, :Sq0].astype(v.dtype)


# ------------------------------------------------------- attention dispatch
# Shortest sequence the kernel takes.  On a TPU v5e, one layer's attention
# at 4,096 tokens (forward and backward under the step's remat policy) was
# faster on the XLA path at S = 128 and 256 (one 512-block, no scan) and
# faster on the kernel from S = 512 up (PERF.md, the S = 128 decision).
FLASH_MIN_SEQ = 512
ATTENTION_PATHS = ("pallas_flash", "xla_blockwise")
# The kernel's output and softmax statistics carry this name, so that a remat
# policy can keep them and the backward pass need not rerun the forward.
FLASH_RESIDUALS = "flash_residuals"

_path_tally: collections.Counter = collections.Counter()

# An identity on the query that counts its path when it is lowered.
# ``lax.platform_dependent`` traces both branches but lowers only the target
# platform's, so a count taken while tracing would see both.  It marks q, not
# the output: every pass needs q (the backward too), while an output that
# only a gradient depends on is dead code and would drop the count.
_path_p = jex_core.Primitive("attention_path")
_path_p.def_impl(lambda x, *, path: x)
_path_p.def_abstract_eval(lambda x, *, path: x)
ad.primitive_jvps[_path_p] = (
    lambda primals, tangents, *, path: (_path_p.bind(primals[0], path=path), tangents[0])
)


def _lower_path(ctx, x, *, path):
    _path_tally[path] += 1
    return [x]


mlir.register_lowering(_path_p, _lower_path)


def attention_path_tally() -> dict[str, int]:
    """Attention calls lowered per path since the process started.

    A call is counted each time a program holding it is lowered (once per
    jit compile; a hit in the persistent compile cache is still lowered), so
    a layer scan counts its body, not each layer.
    """
    return {p: _path_tally[p] for p in ATTENTION_PATHS}


def _flash_fits(q, k, v, *, causal: bool, window: int, q_offset: int) -> bool:
    """Whether the Pallas kernel computes this call: the same attention as
    ``blockwise_attention``, up to bf16 rounding."""
    B, Hq, Sq, hd = q.shape
    _, Hkv, Skv, hdv = v.shape
    return (
        causal and window == 0 and q_offset == 0 and Hq == Hkv
        and k.shape[-1] == hd == hdv and Sq == Skv
        and Sq % 128 == 0 and Sq >= FLASH_MIN_SEQ
        and (hd <= 128 or hd % 128 == 0)
    )


def _flash_block(seq: int) -> int:
    """The kernel's tile for a sequence of ``seq``: the largest of 512, 256
    and 128 that divides it (512 was fastest of the three in every pass at
    S = 2048 on a TPU v5e, PERF.md)."""
    return next(b for b in (512, 256, 128) if seq % b == 0)


def flash_causal_attention(q, k, v):
    """Causal self-attention by the Pallas splash-attention kernel
    (``jax.experimental.pallas.ops.tpu.splash_attention``), forward and fused
    backward.  q, k, v: (B, H, S, hd) in the activation dtype; scores,
    softmax statistics and accumulators are float32.  TPU only (or under
    ``pltpu.force_tpu_interpret_mode``); ``attention`` decides when.

    Pallas is imported here, on the first trace, not with this module: it
    takes about 1.5 s to import, which callers that never trace an
    attention that fits should not pay."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash

    B, H, S, hd = q.shape
    b = _flash_block(S)
    kernel = splash.make_splash_mha_single_device(
        splash.MultiHeadMask([splash.CausalMask((S, S))] * H),
        block_sizes=splash.BlockSizes(
            block_q=b, block_kv=b, block_q_dkv=b, block_kv_dkv=b, use_fused_bwd_kernel=True,
        ),
        residual_checkpoint_name=FLASH_RESIDUALS,
    )
    with jax.named_scope("flash"):
        # the kernel takes one sequence's (H, S, hd) and no scale: scale q as
        # blockwise_attention does, in the activation dtype
        return jax.vmap(kernel)(q * hd ** -0.5, k, v)


def _mesh_spec(mesh, q):
    """The shard_map spec of q, k and v on ``mesh`` (rows over the data axes,
    heads over ``model``), or None where a device's share does not divide."""
    data = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    model = "model" if "model" in mesh.axis_names else None
    rows = math.prod(mesh.shape[a] for a in data)
    heads = mesh.shape[model] if model else 1
    if q.shape[0] % rows or q.shape[1] % heads:
        return None
    return P(data or None, model, None, None)


def sharded_flash_attention(q, k, v, mesh):
    """``flash_causal_attention`` on every device of ``mesh``, each on its own
    rows and heads; q, k, v must divide as ``_mesh_spec`` requires."""
    spec = _mesh_spec(mesh, q)
    return jax.shard_map(flash_causal_attention, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)


def attention(
    q, k, v, *, causal: bool = True, window: int = 0, q_offset: int = 0, mesh=None,
    **blockwise,
):
    """Attention by the path that fits: the Pallas kernel on a TPU where
    ``_flash_fits`` (and, on the model's ``mesh``, where each device's share
    divides), else ``blockwise_attention`` (which takes the remaining keyword
    arguments)."""

    def xla(q, k, v):
        q = _path_p.bind(q, path="xla_blockwise")
        return blockwise_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset, **blockwise
        )

    if (not _flash_fits(q, k, v, causal=causal, window=window, q_offset=q_offset)
            or (mesh is not None and _mesh_spec(mesh, q) is None)):
        return xla(q, k, v)
    flash = flash_causal_attention if mesh is None else partial(sharded_flash_attention, mesh=mesh)

    def kernel(q, k, v):
        return flash(_path_p.bind(q, path="pallas_flash"), k, v)

    return lax.platform_dependent(q, k, v, tpu=kernel, default=xla)


def decode_attention(q, k_cache, v_cache, valid_len, *, window: int = 0):
    """Single-position attention against a cache.

    q: (B, Hq, 1, hd); caches: (B, Hkv, S, hd); ``valid_len``: scalar or (B,)
    number of valid cache positions (the new token lives at valid_len - 1).
    """
    B, Hq, _, hd = q.shape
    _, Hkv, S, hdv = v_cache.shape
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, 1, hd) * (hd ** -0.5)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_cache, preferred_element_type=jnp.float32)
    pos = jnp.arange(S)
    vl = jnp.asarray(valid_len)
    vl = vl[:, None] if vl.ndim == 1 else vl[None]
    mask = pos[None, :] < vl                                     # (B|1, S)
    if window > 0:
        mask &= pos[None, :] > vl - 1 - window
    s = jnp.where(mask[:, None, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhgqk,bhkd->bhgqd", p.astype(v_cache.dtype), v_cache, preferred_element_type=jnp.float32
    )
    return out.reshape(B, Hq, 1, hdv).astype(v_cache.dtype)


# ----------------------------------------------------------------------- MLP
def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def gelu_mlp(x, w_in, b_in, w_out, b_out):
    return jax.nn.gelu(x @ w_in + b_in, approximate=True) @ w_out + b_out


# ----------------------------------------------------------------------- MoE
def moe_block(
    x,
    router_w,
    w_gate,
    w_up,
    w_down,
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    shared: Optional[tuple] = None,
    shard_fn=None,
):
    """Top-k routed experts with capacity, gather/scatter dispatch.

    x: (N, D); expert weights: (E, D, F) / (E, F, D).  FLOPs scale with
    ``N * top_k * capacity_factor``, not with E (gather dispatch — see
    DESIGN.md §6.5).  ``shared`` = (w_gate, w_up, w_down) always-on experts.
    """
    N, D = x.shape
    E, _, F = w_gate.shape
    C = max(1, int(math.ceil(N * top_k / E * capacity_factor)))

    logits = (x.astype(jnp.float32)) @ router_w.astype(jnp.float32)      # (N, E)
    gates, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)       # (N, K)
    gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)

    # position of each (token, k) inside its expert's capacity queue
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)                     # (N, K, E)
    flat = onehot.reshape(N * top_k, E)
    pos = jnp.cumsum(flat, axis=0) - flat                                # (N*K, E)
    slot = (pos * flat).sum(-1).reshape(N, top_k)                        # (N, K)
    keep = slot < C
    slot = jnp.where(keep, slot, C - 1)

    # scatter tokens into (E, C, D) buffers
    buf = jnp.zeros((E, C, D), x.dtype)
    e_flat = idx.reshape(-1)
    s_flat = slot.reshape(-1)
    keep_f = keep.reshape(-1)
    src = jnp.repeat(x, top_k, axis=0) * keep_f[:, None].astype(x.dtype)
    buf = buf.at[e_flat, s_flat].add(src)
    if shard_fn is not None:
        # keep dispatch capacity sharded (otherwise GSPMD may replicate the
        # (E, C, D) buffer across the data axis — see EXPERIMENTS.md §Perf)
        buf = shard_fn(buf)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_up
    )
    y_e = jnp.einsum("ecf,efd->ecd", h, w_down)                          # (E, C, D)
    if shard_fn is not None:
        y_e = shard_fn(y_e)

    gathered = y_e[e_flat, s_flat]                                       # (N*K, D)
    gathered = gathered * (gates.reshape(-1) * keep_f).astype(x.dtype)[:, None]
    y = gathered.reshape(N, top_k, D).sum(1)

    if shared is not None:
        sg, su, sd = shared
        y = y + swiglu(x, sg, su, sd)

    # load-balancing auxiliary loss (Switch-style), returned for training
    me = jax.nn.softmax(logits, -1).mean(0)
    ce = (onehot.sum(1).astype(jnp.float32)).mean(0) / top_k
    aux = E * jnp.sum(me * ce)
    return y, aux
