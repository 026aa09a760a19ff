"""Plain float32 reference of a Qwen2-architecture decoder (Qwen1.5), for training.

Written from the published architecture (Hugging Face ``Qwen2ForCausalLM``)
and independent of the system under test: it imports nothing of it.  Every
matrix product runs at ``Precision.HIGHEST``, since on a TPU a float32
product otherwise runs in bfloat16.

* ``param_spec(config)`` — the parameter tree (shapes and initial scales) in
  the stacked layout the trainer uses: ``layers`` holds every layer's leaves
  with a leading layer axis.
* ``train(config, optimizer, init, batches, low=False)`` — follows the
  first steps of training: loss, gradient, AdamW.  Returns each step's loss,
  each leaf's norm of the first gradient as the optimizer takes it (after
  clipping), and each leaf's norm of the change of the parameters over all
  the steps.

``low=True`` is the control: the same steps with every matrix product's
operands rounded to float8 (e4m3), the precision below the configuration's
bfloat16.

Memory: the gradient is taken over blocks of whole sequences, each layer is
rematerialised in the backward pass and the loss is taken over blocks of
rows, so the reference fits one chip beside nothing else at the
benchmark's sizes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
LOSS_ROWS = 512          # rows of the flattened batch per block of the loss
MICRO_TOKENS = 2048      # tokens per block of the batch in the gradient


def dims(c: dict) -> dict:
    D, H = c["hidden_size"], c["num_attention_heads"]
    return dict(L=c["num_hidden_layers"], D=D, H=H, Hkv=c["num_key_value_heads"],
                hd=D // H, F=c["intermediate_size"], V=c["vocab_size"],
                eps=c["rms_norm_eps"], theta=c["rope_theta"],
                tied=c["tie_word_embeddings"])


def param_spec(c: dict) -> dict:
    """Leaf -> (shape, init): init is "ones" or the std of a normal draw."""
    d = dims(c)
    L, D, H, Hkv, hd, F, V = d["L"], d["D"], d["H"], d["Hkv"], d["hd"], d["F"], d["V"]
    attn = {
        "ln": ((L, D), "ones"),
        "wq": ((L, D, H * hd), D ** -0.5),
        "wk": ((L, D, Hkv * hd), D ** -0.5),
        "wv": ((L, D, Hkv * hd), D ** -0.5),
        "wo": ((L, H * hd, D), (H * hd) ** -0.5),
        "bq": ((L, H * hd), 0.02),
        "bk": ((L, Hkv * hd), 0.02),
        "bv": ((L, Hkv * hd), 0.02),
    }
    mlp = {
        "ln": ((L, D), "ones"),
        "w_gate": ((L, D, F), D ** -0.5),
        "w_up": ((L, D, F), D ** -0.5),
        "w_down": ((L, F, D), F ** -0.5),
    }
    spec = {"embed": ((V, D), 0.02), "final_ln": ((D,), "ones"),
            "layers": {"attn": attn, "mlp": mlp}}
    if not d["tied"]:
        spec["lm_head"] = ((D, V), D ** -0.5)
    return spec


# ------------------------------------------------------------------ forward
def _mm(eq, a, b, low):
    if low:
        return jnp.einsum(eq, a.astype(F8), b.astype(F8), preferred_element_type=jnp.float32)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, theta):
    """x: (B, S, heads, hd); Hugging Face's rotate-half form."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    return x * jnp.cos(ang) + _rotate_half(x) * jnp.sin(ang)


def _layer(d, low, x, p):
    B, S, D = x.shape
    H, Hkv, hd = d["H"], d["Hkv"], d["hd"]
    a, m = p["attn"], p["mlp"]
    h = _rms(x, a["ln"], d["eps"])
    q = (_mm("bsd,de->bse", h, a["wq"], low) + a["bq"]).reshape(B, S, H, hd)
    k = (_mm("bsd,de->bse", h, a["wk"], low) + a["bk"]).reshape(B, S, Hkv, hd)
    v = (_mm("bsd,de->bse", h, a["wv"], low) + a["bv"]).reshape(B, S, Hkv, hd)
    q, k = _rope(q, d["theta"]), _rope(k, d["theta"])
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, low) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, low).reshape(B, S, H * hd)
    x = x + _mm("bse,ed->bsd", o, a["wo"], low)
    h = _rms(x, m["ln"], d["eps"])
    f = jax.nn.silu(_mm("bsd,df->bsf", h, m["w_gate"], low)) * _mm("bsd,df->bsf", h, m["w_up"], low)
    return x + _mm("bsf,fd->bsd", f, m["w_down"], low)


def loss(c: dict, params, tokens, labels, low: bool = False):
    """Mean next-token cross-entropy over every position of the batch."""
    d = dims(c)
    x = params["embed"][tokens]
    body = jax.checkpoint(lambda x, p: (_layer(d, low, x, p), None))
    x, _ = lax.scan(body, x, params["layers"])
    h = _rms(x, params["final_ln"], d["eps"]).reshape(-1, d["D"])
    head = params["embed"].T if d["tied"] else params["lm_head"]
    y = labels.reshape(-1)
    n = h.shape[0]
    rows = min(LOSS_ROWS, n)
    assert n % rows == 0, (n, rows)

    @jax.checkpoint
    def block(hy):
        hb, yb = hy
        logits = _mm("nd,dv->nv", hb, head, low)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    sums = lax.map(block, (h.reshape(n // rows, rows, -1), y.reshape(n // rows, rows)))
    return jnp.sum(sums) / n


# ---------------------------------------------------------------- optimizer
def _adamw(o: dict, p, g, mu, nu, count):
    """One AdamW step with global-norm clipping and linear warm-up."""
    leaves = jax.tree.leaves(g)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
    scale = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    lr = o["lr"] * jnp.minimum(1.0, (count + 1) / max(1, o["warmup_steps"]))
    t = (count + 1).astype(jnp.float32)
    b1c, b2c = 1 - o["b1"] ** t, 1 - o["b2"] ** t
    g = jax.tree.map(lambda x: x * scale, g)
    mu = jax.tree.map(lambda m, x: o["b1"] * m + (1 - o["b1"]) * x, mu, g)
    nu = jax.tree.map(lambda n, x: o["b2"] * n + (1 - o["b2"]) * x * x, nu, g)
    p = jax.tree.map(
        lambda w, m, n: w - lr * ((m / b1c) / (jnp.sqrt(n / b2c) + o["eps"])
                                  + o["weight_decay"] * w),
        p, mu, nu)
    return p, mu, nu, g


def _norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def _value_and_grad(c, p, tokens, labels, low):
    """Loss and gradient of the whole batch, taken over blocks of at most
    ``MICRO_TOKENS`` tokens of whole sequences: equal blocks, so the mean of
    their means is the batch's mean."""
    B, S = tokens.shape
    m = max(1, min(B, MICRO_TOKENS // S))
    while B % m:
        m -= 1
    vg = jax.value_and_grad(lambda w, t, y: loss(c, w, t, y, low))

    def body(acc, ty):
        v, g = vg(p, *ty)
        return (acc[0] + v, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, p))
    (v, g), _ = lax.scan(body, zero, (tokens.reshape(B // m, m, S), labels.reshape(B // m, m, S)))
    n = B // m
    return v / n, jax.tree.map(lambda x: x / n, g)


def make_step(c: dict, o: dict, low: bool = False):
    def step(p, mu, nu, count, tokens, labels):
        value, g = _value_and_grad(c, p, tokens, labels, low)
        p, mu, nu, gc = _adamw(o, p, g, mu, nu, count)
        return p, mu, nu, value, _norms(gc)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def train(c: dict, o: dict, init, batches, low: bool = False, step=None) -> dict:
    """Follow ``len(batches)`` steps from the weights ``init()`` returns.

    ``init`` is called twice, at the start and for the change at the end, so
    that no copy of the first weights is held through the steps.  ``step``,
    where given, is ``make_step(c, o, low)`` built once for many calls."""
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
    p = jax.jit(f32)(init())
    mu = jax.tree.map(jnp.zeros_like, p)
    nu = jax.tree.map(jnp.zeros_like, p)
    step = step or make_step(c, o, low)
    losses, first = [], None
    for i, (tokens, labels) in enumerate(batches):
        p, mu, nu, value, gn = step(p, mu, nu, jnp.int32(i),
                                    jnp.asarray(tokens), jnp.asarray(labels))
        losses.append(float(value))
        if i == 0:
            first = jax.device_get(gn)
    del mu, nu
    change = jax.jit(lambda a, b: _norms(jax.tree.map(lambda x, y: x - y.astype(jnp.float32),
                                                      a, b)))(p, init())
    return {"losses": losses, "grad_norms": first, "change_norms": jax.device_get(change)}
