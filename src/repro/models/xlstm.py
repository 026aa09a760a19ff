"""xLSTM: mLSTM (matrix memory, chunkwise-parallel) + sLSTM (scalar, scanned).

The mLSTM recurrence (xLSTM paper, exp-gating stabilized)

    C_t = f_t C_{t-1} + i_t v_t k_t^T        n_t = f_t n_{t-1} + i_t k_t
    h_t = (C_t q̃_t) / max(|n_t · q̃_t|, 1)   q̃ = q / sqrt(d)

admits a chunkwise-parallel form: within a chunk of length L all pair weights
are D_{ts} = exp(b_t - b_s + i_s - m_t) (b = cumulative log-f, m = running
max stabilizer), computed as an (L, L) masked matrix; across chunks a small
scan carries (C, n, m).  This is the TPU-friendly layout — the same math the
official CUDA kernels implement, reorganised for MXU-sized matmuls.

sLSTM blocks (1 per ``slstm_every``) are genuinely sequential (recurrent
nonlinearity) and run as a ``lax.scan`` over time.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from . import params as PM
from .layers import rms_norm

TP = "model"
_NEG = -1e30


# ---------------------------------------------------------------- mLSTM core
def mlstm_chunked(q, k, v, i_raw, log_f, *, chunk: int):
    """q,k: (B,H,S,dqk); v: (B,H,S,dv); i_raw, log_f: (B,H,S). Returns h.

    Chunkwise-parallel stabilized mLSTM (see module docstring).
    """
    B, H, S, dqk = q.shape
    dv = v.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    nc = S // L
    f32 = jnp.float32

    q = q.astype(f32) * (dqk ** -0.5)
    k = k.astype(f32)
    v = v.astype(f32)
    i_raw = i_raw.astype(f32)
    log_f = log_f.astype(f32)

    def to_chunks(x):
        return x.reshape(B, H, nc, L, *x.shape[3:]).transpose(2, 0, 1, 3, *range(4, x.ndim + 1))

    qc, kc, vc = to_chunks(q), to_chunks(k), to_chunks(v)      # (nc,B,H,L,d)
    ic, fc = to_chunks(i_raw), to_chunks(log_f)                # (nc,B,H,L)

    C0 = jnp.zeros((B, H, dqk, dv), f32)
    n0 = jnp.zeros((B, H, dqk), f32)
    m0 = jnp.full((B, H), _NEG, f32)

    tri = jnp.tril(jnp.ones((L, L), bool))

    def chunk_step(carry, xs):
        C, n, m_prev = carry
        qi, ki, vi, ii, fi = xs
        b = jnp.cumsum(fi, axis=-1)                            # (B,H,L) inclusive
        r = lax.cummax(ii - b, axis=2)                         # running max_s (i_s - b_s)
        m_t = b + jnp.maximum(m_prev[..., None], r)            # (B,H,L)

        # intra-chunk pair weights  D_ts = exp(b_t - b_s + i_s - m_t), s <= t
        logD = b[..., :, None] - b[..., None, :] + ii[..., None, :] - m_t[..., :, None]
        D = jnp.where(tri[None, None], jnp.exp(logD), 0.0)     # (B,H,L,L)

        scores = jnp.einsum("bhtd,bhsd->bhts", qi, ki) * D
        inter_scale = jnp.exp(b + m_prev[..., None] - m_t)     # (B,H,L)
        num = jnp.einsum("bhts,bhsd->bhtd", scores, vi)
        num = num + inter_scale[..., None] * jnp.einsum("bhtd,bhdv->bhtv", qi, C)
        den = scores.sum(-1) + inter_scale * jnp.einsum("bhtd,bhd->bht", qi, n)
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]

        # chunk-end state update (t = L)
        m_next = b[..., -1] + jnp.maximum(m_prev, r[..., -1])
        w_state = jnp.exp(b[..., -1:] - b + ii - m_next[..., None])   # (B,H,L)
        decay = jnp.exp(b[..., -1] + m_prev - m_next)
        C_next = decay[..., None, None] * C + jnp.einsum("bhs,bhsd,bhsv->bhdv", w_state, ki, vi)
        n_next = decay[..., None] * n + jnp.einsum("bhs,bhsd->bhd", w_state, ki)
        return (C_next, n_next, m_next), h

    _, hs = lax.scan(chunk_step, (C0, n0, m0), (qc, kc, vc, ic, fc))
    # (nc,B,H,L,dv) -> (B,H,S,dv)
    return hs.transpose(1, 2, 0, 3, 4).reshape(B, H, S, dv)


def mlstm_decode(q, k, v, i_raw, log_f, state):
    """Single-token mLSTM update.  q,k: (B,H,dqk); v: (B,H,dv); gates (B,H)."""
    C, n, m = state
    dqk = q.shape[-1]
    f32 = jnp.float32
    q = q.astype(f32) * (dqk ** -0.5)
    k, v = k.astype(f32), v.astype(f32)
    m_new = jnp.maximum(log_f + m, i_raw)
    f_s = jnp.exp(log_f + m - m_new)
    i_s = jnp.exp(i_raw - m_new)
    C = f_s[..., None, None] * C + i_s[..., None, None] * (k[..., :, None] * v[..., None, :])
    n = f_s[..., None] * n + i_s[..., None] * k
    num = jnp.einsum("bhd,bhdv->bhv", q, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q, n)), jnp.exp(-m_new))
    return num / den[..., None], (C, n, m_new)


# -------------------------------------------------------------------- model
class XLSTM:
    """48-block stack: one sLSTM block per ``slstm_every``, rest mLSTM.

    Stack = scan over ``n_layers // slstm_every`` super-blocks, each an inner
    scan over (slstm_every - 1) mLSTM blocks followed by one sLSTM block.
    """

    def __init__(self, cfg: ModelConfig, *, model_axis: int = 16, mesh=None):
        self.cfg = cfg
        self.mesh = mesh
        self.model_axis = model_axis
        D = cfg.d_model
        self.ed = cfg.ssm.expand * D          # mLSTM inner width
        self.H = cfg.n_heads
        self.dv = self.ed // self.H
        self.dqk = self.dv // 2
        self.sh = cfg.n_heads                 # sLSTM heads
        self.sdh = D // self.sh
        self.s_ff = 2688                      # ~4/3 * d, MXU-aligned

    def _dp(self):
        if self.mesh is None:
            return ("pod", "data")
        return tuple(a for a in ("pod", "data") if a in self.mesh.axis_names) or None

    def _shard(self, x, *spec):
        if self.mesh is None:
            return x
        return lax.with_sharding_constraint(x, jax.sharding.NamedSharding(self.mesh, P(*spec)))

    # -------------------------------------------------------------- layout
    def mlstm_layout(self) -> dict:
        D, ed, H = self.cfg.d_model, self.ed, self.H
        return {
            "ln": PM.ParamInfo((D,), P(None), "ones"),
            "w_up": PM.ParamInfo((D, 2 * ed), P(None, TP)),
            "conv": PM.ParamInfo((self.cfg.ssm.conv_width, ed), P(None, TP), scale=0.3),
            "wq": PM.ParamInfo((ed, H * self.dqk), P(TP, None)),
            "wk": PM.ParamInfo((ed, H * self.dqk), P(TP, None)),
            "wv": PM.ParamInfo((ed, H * self.dv), P(TP, None)),
            "w_i": PM.ParamInfo((ed, H), P(TP, None), scale=0.02),
            "b_i": PM.ParamInfo((H,), P(None), "zeros"),
            "w_f": PM.ParamInfo((ed, H), P(TP, None), scale=0.02),
            "b_f": PM.ParamInfo((H,), P(None), init="ones", scale=3.0),
            "out_ln": PM.ParamInfo((ed,), P(TP), "ones"),
            "w_down": PM.ParamInfo((ed, D), P(TP, None)),
        }

    def slstm_layout(self) -> dict:
        D, sh, dh = self.cfg.d_model, self.sh, self.sdh
        return {
            "ln": PM.ParamInfo((D,), P(None), "ones"),
            # sh=4 heads cannot shard a 16-way axis; shard the dh dims
            "w_gates": PM.ParamInfo((D, sh, dh, 4), P(None, None, TP, None)),
            "r_gates": PM.ParamInfo((sh, dh, dh, 4), P(None, TP, None, None), scale=0.02),
            "b_gates": PM.ParamInfo((sh, dh, 4), P(None, TP, None), "zeros"),
            "out_ln": PM.ParamInfo((D,), P(None), "ones"),
            "w_out": PM.ParamInfo((D, D), P(None, TP)),
            "ffn_ln": PM.ParamInfo((D,), P(None), "ones"),
            "ffn_gate": PM.ParamInfo((D, self.s_ff), P(None, TP)),
            "ffn_up": PM.ParamInfo((D, self.s_ff), P(None, TP)),
            "ffn_down": PM.ParamInfo((self.s_ff, D), P(TP, None)),
        }

    def layout(self) -> dict:
        cfg = self.cfg
        every = cfg.ssm.slstm_every
        assert cfg.n_layers % every == 0
        groups = cfg.n_layers // every
        div_v = cfg.vocab % self.model_axis == 0
        div_d = cfg.d_model % self.model_axis == 0
        emb_spec = P(TP, None) if div_v else (P(None, TP) if div_d else P(None, None))
        head_spec = P(None, TP) if div_v else (P(TP, None) if div_d else P(None, None))
        return {
            "embed": PM.ParamInfo((cfg.vocab, cfg.d_model), emb_spec, scale=0.02),
            "groups": PM.stack(
                groups,
                {"mlstm": PM.stack(every - 1, self.mlstm_layout()), "slstm": self.slstm_layout()},
            ),
            "final_ln": PM.ParamInfo((cfg.d_model,), P(None), "ones"),
            "lm_head": PM.ParamInfo((cfg.d_model, cfg.vocab), head_spec, scale=0.02),
        }

    # ------------------------------------------------------------- blocks
    def _conv(self, x, w):
        """Causal depthwise conv along time.  x: (B,S,ed); w: (W,ed)."""
        W = w.shape[0]
        pad = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
        out = sum(pad[:, i : i + x.shape[1]] * w[i] for i in range(W))
        return out

    def _mlstm_qkvif(self, p, xc, xv):
        B, S, _ = xc.shape
        H = self.H
        q = (xc @ p["wq"]).reshape(B, S, H, self.dqk).transpose(0, 2, 1, 3)
        k = (xc @ p["wk"]).reshape(B, S, H, self.dqk).transpose(0, 2, 1, 3)
        v = (xv @ p["wv"]).reshape(B, S, H, self.dv).transpose(0, 2, 1, 3)
        i_raw = (xc @ p["w_i"] + p["b_i"]).transpose(0, 2, 1)
        log_f = jax.nn.log_sigmoid((xc @ p["w_f"] + p["b_f"])).transpose(0, 2, 1)
        return q, k, v, i_raw, log_f

    def _mlstm_block(self, p, x):
        cfg = self.cfg
        B, S, D = x.shape
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        up = h @ p["w_up"]
        x_in, z = jnp.split(up, 2, axis=-1)
        xc = jax.nn.silu(self._conv(x_in, p["conv"]))
        q, k, v, i_raw, log_f = self._mlstm_qkvif(p, xc, x_in)
        hh = mlstm_chunked(q, k, v, i_raw, log_f, chunk=cfg.ssm.chunk)
        hh = hh.transpose(0, 2, 1, 3).reshape(B, S, self.ed).astype(x.dtype)
        hh = rms_norm(hh, p["out_ln"], cfg.norm_eps) * jax.nn.silu(z)
        return x + hh @ p["w_down"]

    def _slstm_block(self, p, x):
        cfg = self.cfg
        B, S, D = x.shape
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        # input-driven gate preactivations; the recurrent term (depends on
        # h_{t-1}) is added inside the scan
        gates = jnp.einsum(
            "bsd,dhDg->bshDg", h.astype(jnp.float32), p["w_gates"].astype(jnp.float32)
        ) + p["b_gates"].astype(jnp.float32)
        state = (
            jnp.zeros((B, self.sh, self.sdh), jnp.float32),
            jnp.zeros((B, self.sh, self.sdh), jnp.float32),
            jnp.full((B, self.sh, self.sdh), _NEG, jnp.float32),
            jnp.zeros((B, self.sh, self.sdh), jnp.float32),
        )
        r = p["r_gates"].astype(jnp.float32)

        def step(carry, g_t):
            c, n, m, h_prev = carry
            g_t = g_t + jnp.einsum("bhd,hdDg->bhDg", h_prev, r)
            z = jnp.tanh(g_t[..., 0])
            i_raw = g_t[..., 1]
            lf = jax.nn.log_sigmoid(g_t[..., 2])
            o = jax.nn.sigmoid(g_t[..., 3])
            m_new = jnp.maximum(lf + m, i_raw)
            i_s = jnp.exp(i_raw - m_new)
            f_s = jnp.exp(lf + m - m_new)
            c = f_s * c + i_s * z
            n = f_s * n + i_s
            h_new = o * c / jnp.maximum(n, 1e-6)
            return (c, n, m_new, h_new), h_new

        _, hs = lax.scan(step, state, jnp.moveaxis(gates, 1, 0))
        hh = jnp.moveaxis(hs, 0, 1).reshape(B, S, D).astype(x.dtype)
        x = x + rms_norm(hh, p["out_ln"], cfg.norm_eps) @ p["w_out"]
        # post-FFN (xLSTM sLSTM blocks carry a ~4/3 GeGLU projection)
        h = rms_norm(x, p["ffn_ln"], cfg.norm_eps)
        return x + (jax.nn.silu(h @ p["ffn_gate"]) * (h @ p["ffn_up"])) @ p["ffn_down"]

    def _remat(self, fn):
        if self.cfg.remat == "none":
            return fn
        policy = (
            jax.checkpoint_policies.nothing_saveable
            if self.cfg.remat == "full"
            else jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
        return jax.checkpoint(fn, policy=policy)

    # ------------------------------------------------------------ forward
    def backbone(self, params, x):
        m_block = self._remat(self._mlstm_block)
        s_block = self._remat(self._slstm_block)

        def group_step(h, gp):
            def inner(hh, mp):
                return m_block(mp, hh), None

            h, _ = lax.scan(inner, h, gp["mlstm"])
            h = s_block(gp["slstm"], h)
            return self._shard(h, self._dp(), None, None), None

        x, _ = lax.scan(group_step, x, params["groups"])
        return rms_norm(x, params["final_ln"], self.cfg.norm_eps)

    def loss(self, params, batch):
        cfg = self.cfg
        x = params["embed"][batch["tokens"]].astype(jnp.dtype(cfg.dtype))
        x = self._shard(x, self._dp(), None, None)
        h = self.backbone(params, x)
        logits = (h @ params["lm_head"]).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, batch["labels"][..., None], axis=-1)[..., 0]
        nll = (lse - gold).mean()
        return nll, {"nll": nll, "aux": 0.0}

    def prefill(self, params, batch):
        cfg = self.cfg
        x = params["embed"][batch["tokens"]].astype(jnp.dtype(cfg.dtype))
        h = self.backbone(params, x)
        return (h[:, -1:] @ params["lm_head"]).astype(jnp.float32)

    # -------------------------------------------------------------- decode
    def cache_layout(self, batch: int, seq: int) -> dict:
        """Recurrent state: O(1) in sequence length (the SSM long_500k win)."""
        cfg = self.cfg
        every = cfg.ssm.slstm_every
        groups = cfg.n_layers // every
        dp = self._dp()
        W = cfg.ssm.conv_width
        # H (4 heads) does not divide a 16-way model axis; shard the large
        # per-head state dims on 'model' instead
        m_state = {
            "C": PM.ParamInfo(
                (batch, self.H, self.dqk, self.dv), P(dp, None, TP, None), "zeros", dtype="float32"
            ),
            "n": PM.ParamInfo((batch, self.H, self.dqk), P(dp, None, TP), "zeros", dtype="float32"),
            "m": PM.ParamInfo((batch, self.H), P(dp, None), "zeros", dtype="float32"),
            "conv": PM.ParamInfo((batch, W - 1, self.ed), P(dp, None, TP), "zeros"),
        }
        s_state = {
            "c": PM.ParamInfo(
                (batch, self.sh, self.sdh), P(dp, None, TP), "zeros", dtype="float32"
            ),
            "n": PM.ParamInfo(
                (batch, self.sh, self.sdh), P(dp, None, TP), "zeros", dtype="float32"
            ),
            "m": PM.ParamInfo(
                (batch, self.sh, self.sdh), P(dp, None, TP), "zeros", dtype="float32"
            ),
            "h": PM.ParamInfo(
                (batch, self.sh, self.sdh), P(dp, None, TP), "zeros", dtype="float32"
            ),
        }
        return {
            "groups": PM.stack(groups, {"mlstm": PM.stack(every - 1, m_state), "slstm": s_state})
        }

    def decode_step(self, params, batch):
        cfg = self.cfg
        tokens, cache = batch["tokens"], batch["cache"]
        B = tokens.shape[0]
        x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))  # (B,1,D)

        def m_decode(p, h, st):
            hx = rms_norm(h, p["ln"], cfg.norm_eps)
            up = hx @ p["w_up"]
            x_in, z = jnp.split(up, 2, axis=-1)                   # (B,1,ed)
            conv_buf = jnp.concatenate([st["conv"], x_in], axis=1)
            W = p["conv"].shape[0]
            xc = jax.nn.silu(sum(conv_buf[:, i : i + 1] * p["conv"][i] for i in range(W)))
            q, k, v, i_raw, log_f = self._mlstm_qkvif(p, xc, x_in)
            hh, (C, n, m) = mlstm_decode(
                q[:, :, 0], k[:, :, 0], v[:, :, 0], i_raw[:, :, 0], log_f[:, :, 0],
                (st["C"], st["n"], st["m"]),
            )
            hh = hh.reshape(B, 1, self.ed).astype(h.dtype)
            hh = rms_norm(hh, p["out_ln"], cfg.norm_eps) * jax.nn.silu(z)
            new = {"C": C, "n": n, "m": m, "conv": conv_buf[:, 1:]}
            return h + hh @ p["w_down"], new

        def s_decode(p, h, st):
            hx = rms_norm(h, p["ln"], cfg.norm_eps)[:, 0]
            g = jnp.einsum(
                "bd,dhDg->bhDg", hx.astype(jnp.float32), p["w_gates"].astype(jnp.float32)
            )
            g = g + p["b_gates"].astype(jnp.float32)
            g = g + jnp.einsum("bhd,hdDg->bhDg", st["h"], p["r_gates"].astype(jnp.float32))
            z = jnp.tanh(g[..., 0])
            i_raw = g[..., 1]
            lf = jax.nn.log_sigmoid(g[..., 2])
            o = jax.nn.sigmoid(g[..., 3])
            m_new = jnp.maximum(lf + st["m"], i_raw)
            i_s = jnp.exp(i_raw - m_new)
            f_s = jnp.exp(lf + st["m"] - m_new)
            c = f_s * st["c"] + i_s * z
            n = f_s * st["n"] + i_s
            h_new = o * c / jnp.maximum(n, 1e-6)
            hh = h_new.reshape(B, 1, cfg.d_model).astype(h.dtype)
            h = h + rms_norm(hh, p["out_ln"], cfg.norm_eps) @ p["w_out"]
            hf = rms_norm(h, p["ffn_ln"], cfg.norm_eps)
            h = h + (jax.nn.silu(hf @ p["ffn_gate"]) * (hf @ p["ffn_up"])) @ p["ffn_down"]
            return h, {"c": c, "n": n, "m": m_new, "h": h_new}

        def group_step(h, pc):
            gp, gc = pc

            def inner(hh, mpc):
                mp, mc = mpc
                hh, new = m_decode(mp, hh, mc)
                return hh, new

            h, m_new = lax.scan(inner, h, (gp["mlstm"], gc["mlstm"]))
            h, s_new = s_decode(gp["slstm"], h, gc["slstm"])
            return h, {"mlstm": m_new, "slstm": s_new}

        x, new_groups = lax.scan(group_step, x, (params["groups"], cache["groups"]))
        h = rms_norm(x, params["final_ln"], cfg.norm_eps)
        logits = (h @ params["lm_head"]).astype(jnp.float32)
        return logits, {"groups": new_groups}
