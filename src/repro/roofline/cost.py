"""Analytic cost of the attention, mLSTM and SSD work in a training step.

The dense ``6*N*D`` matmul model of :mod:`repro.roofline.analysis` prices
the parameter matmuls only.  These functions price the sequence-mixing work
it misses: attention over the visited context, the chunked mLSTM scan and
the chunked Mamba-2 SSD scan.  Each returns the three quantities a
``pl.CostEstimate`` declares to the compiler (``flops``, ``bytes_accessed``
and ``transcendentals``) for a flash-style tiling of that work.  The
roofline table generator (:mod:`repro.roofline.table`) sums them per layer.

Conventions (shared with :mod:`repro.roofline.analysis`):

* FLOPs count MXU work only (2 per multiply-accumulate); vector-unit
  elementwise work rides along free.
* ``bytes_accessed`` is HBM traffic of the tiled computation: operand tiles
  are charged once per grid visit (flash attention re-streams K/V once per
  query block — that re-read is its real memory cost), outputs once.
* Masked-out work is *not* charged: causal/windowed attention prices the
  average visited context per query, matching the ``causal_pairs`` block
  enumeration rather than the dense rectangle.

Pure Python, no jax import — loadable from table generation and tests alike.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class KernelCost:
    """The three axes of ``pl.CostEstimate``, as plain floats."""

    flops: float
    bytes_accessed: float
    transcendentals: float = 0.0

    def __add__(self, other: "KernelCost") -> "KernelCost":
        return KernelCost(
            self.flops + other.flops,
            self.bytes_accessed + other.bytes_accessed,
            self.transcendentals + other.transcendentals,
        )

    def scale(self, k: float) -> "KernelCost":
        return KernelCost(self.flops * k, self.bytes_accessed * k, self.transcendentals * k)


ZERO_COST = KernelCost(0.0, 0.0, 0.0)


def avg_context(seq_len: int, kv_len: int, *, causal: bool = True, window: int = 0) -> float:
    """Mean visited KV positions per query row.

    Full attention sees ``kv_len``; causal row ``i`` sees ``i+1`` (mean
    ``(kv_len+1)/2`` for square self-attention); a sliding window of ``w``
    clamps that at ``w`` once past the ramp: exact mean
    ``w - w*(w-1)/(2*seq_len)`` for ``seq_len >= w``.
    """
    if window > 0:
        w = min(window, kv_len)
        if seq_len <= w:
            return (seq_len + 1) / 2.0 if causal else float(kv_len)
        return w - w * (w - 1) / (2.0 * seq_len)
    if causal and seq_len == kv_len:
        return (kv_len + 1) / 2.0
    return float(kv_len)


def flash_attention_cost(
    batch: int,
    q_heads: int,
    q_len: int,
    kv_len: int,
    head_dim: int,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    dtype_bytes: int = 2,
) -> KernelCost:
    """Forward cost of one flash-tiled attention call.

    FLOPs: ``QK^T`` and ``PV`` are each ``2*ctx*head_dim`` per query per
    head; transcendentals: one ``exp`` per visited score.  Bytes: Q and O
    tiles stream once, K/V tiles once per *visited* query block
    (``n_q_blocks * visited_fraction`` re-reads — the flash-attention
    HBM-traffic signature).
    """
    ctx = avg_context(q_len, kv_len, causal=causal, window=window)
    bh = float(batch * q_heads)
    flops = 4.0 * bh * q_len * ctx * head_dim
    transcendentals = bh * q_len * ctx
    n_q_blocks = -(-q_len // max(1, block_q))
    visited = ctx / float(kv_len)
    qo_bytes = 2.0 * bh * q_len * head_dim * dtype_bytes
    kv_bytes = 2.0 * bh * kv_len * head_dim * dtype_bytes * n_q_blocks * visited
    return KernelCost(flops, qo_bytes + kv_bytes, transcendentals)


def mlstm_scan_cost(
    batch: int,
    heads: int,
    seq_len: int,
    d_qk: int,
    d_v: int,
    *,
    chunk: int = 128,
    dtype_bytes: int = 2,
) -> KernelCost:
    """Forward cost of one chunked mLSTM scan (``models.xlstm.mlstm_chunked``).

    Per token per head: intra-chunk pair weights cost ``2*L*(d_qk + d_v)``
    (QK^T over the chunk + the (L,L)@V read-out), the cross-chunk matrix
    memory costs ``4*d_qk*d_v`` (K^T V state update + Q-through-C read).
    One decay ``exp`` per intra-chunk pair.
    """
    L = min(chunk, seq_len)
    bht = float(batch * heads * seq_len)
    flops = bht * (2.0 * L * (d_qk + d_v) + 4.0 * d_qk * d_v)
    transcendentals = bht * L
    io_bytes = bht * (2.0 * d_qk + 2.0 * d_v + 2.0) * dtype_bytes
    return KernelCost(flops, io_bytes, transcendentals)


def ssd_scan_cost(
    batch: int,
    heads: int,
    seq_len: int,
    head_channels: int,
    state_dim: int,
    *,
    chunk: int = 128,
    dtype_bytes: int = 2,
) -> KernelCost:
    """Forward cost of one chunked ``models.hymba.ssd_scan`` (Mamba-2 SSD) call.

    Per token per head: intra-chunk decay-weighted pair read-out
    ``2*L*head_channels``, plus the carried (chd, N) state — outer-product
    update and C-read — at ``4*head_channels*state_dim``.
    """
    L = min(chunk, seq_len)
    bht = float(batch * heads * seq_len)
    flops = bht * head_channels * (2.0 * L + 4.0 * state_dim)
    transcendentals = bht * L
    io_bytes = bht * (2.0 * head_channels + 3.0 * state_dim) * dtype_bytes
    return KernelCost(flops, io_bytes, transcendentals)


def swiglu_cost(
    tokens: int, d_model: int, d_ff: int, *, dtype_bytes: int = 2
) -> KernelCost:
    """Forward cost of one ``models.layers.swiglu`` call (three matmuls + gate)."""
    flops = 6.0 * tokens * d_model * d_ff
    io_bytes = (2.0 * tokens * d_model + 3.0 * d_model * d_ff) * dtype_bytes
    return KernelCost(flops, io_bytes, float(tokens * d_ff))
