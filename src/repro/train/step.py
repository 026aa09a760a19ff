"""train_step builder: loss + grad + AdamW update, pjit-ready.

The returned function is the dry-run's ``train_step`` lowering target:
``(params, opt_state, batch) -> (params, opt_state, metrics)`` with donated
carry buffers.  Sharding comes from the model layout specs + ZeRO opt-state
specs; activations follow the in-model constraints.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .optimizer import AdamWConfig, adamw_update, init_opt_state


def make_train_step(model, opt_cfg: Optional[AdamWConfig] = None):
    opt_cfg = opt_cfg or AdamWConfig()

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            loss, metrics = model.loss(p, batch)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_metrics = adamw_update(grads, opt_state, params, opt_cfg)
        metrics = {**metrics, **opt_metrics, "loss": loss}
        return new_params, new_opt, metrics

    return train_step


def make_eval_step(model):
    def eval_step(params, batch):
        loss, metrics = model.loss(params, batch)
        return {**metrics, "loss": loss}

    return eval_step


def init_train_state(model, key, opt_cfg: Optional[AdamWConfig] = None):
    """Real-array initialisation (examples / integration tests)."""
    from ..models import params as PM

    opt_cfg = opt_cfg or AdamWConfig()
    params = PM.materialize(model.layout(), key, model.cfg.dtype)
    return params, init_opt_state(params, opt_cfg)


# ---------------------------------------------------------------------------
# Compute-plane integration path (ISSUE 10): feed a *real* train step from
# bytes served by the cache (``FileDataset.read_item_bytes`` on a
# materialized store), and read back the compiled step's XLA cost analysis
# to validate the analytic roofline table against an actually-executed step.
# ---------------------------------------------------------------------------

def token_batch_from_bytes(payloads: Sequence[bytes], seq_len: int, vocab: int) -> dict:
    """Decode raw item payloads (int32 records) into a ``{tokens, labels}`` batch.

    Each payload is one dataset item as stored on the stripe store: a run of
    little-endian int32 token ids, ``seq_len`` of which form one training
    sequence (ids are folded into ``[0, vocab)`` so any byte payload is a
    legal batch).  Labels are next-token targets.
    """
    rows = []
    for p in payloads:
        toks = np.frombuffer(p, dtype=np.int32)[:seq_len]
        if len(toks) < seq_len:
            raise ValueError(
                f"item payload holds {len(toks)} int32 tokens, need {seq_len}"
            )
        rows.append(toks)
    tokens = np.abs(np.stack(rows)) % vocab
    labels = np.roll(tokens, -1, axis=1)
    return {
        "tokens": jnp.asarray(tokens, jnp.int32),
        "labels": jnp.asarray(labels, jnp.int32),
    }


def compiled_step_flops(model, batch, *, opt_cfg: Optional[AdamWConfig] = None,
                        key=None) -> float:
    """Compile one real train step on ``batch``; return XLA's FLOP count.

    The executable is the genuine jit of :func:`make_train_step` — the same
    lowering an accelerator run would use — so ``cost_analysis()['flops']``
    prices the step as compiled, not as modelled.  Divided by
    ``PEAK_FLOPS`` this is the roofline compute term the calibration table
    must agree with (``tests/test_compute_plane.py`` asserts the tolerance).
    """
    opt_cfg = opt_cfg or AdamWConfig()
    key = key if key is not None else jax.random.PRNGKey(0)
    params, opt_state = init_train_state(model, key, opt_cfg)
    compiled = jax.jit(make_train_step(model, opt_cfg)).lower(
        params, opt_state, batch
    ).compile()
    return float(compiled.cost_analysis().get("flops", 0.0))


def compiled_step_costs(model, batch, *, opt_cfg: Optional[AdamWConfig] = None,
                        key=None) -> dict:
    """Trip-count-aware costs of one compiled train step.

    ``cost_analysis()`` visits a scan-over-layers ``while`` body once, so it
    undercounts any scanned model; this walks the optimized HLO with
    :mod:`repro.roofline.hlo_walk` (multiplying loop bodies by their trip
    counts) and returns the walker's dict plus ``xla_flops`` (the raw
    ``cost_analysis`` figure, kept for comparison).
    """
    from ..roofline import hlo_walk

    opt_cfg = opt_cfg or AdamWConfig()
    key = key if key is not None else jax.random.PRNGKey(0)
    params, opt_state = init_train_state(model, key, opt_cfg)
    compiled = jax.jit(make_train_step(model, opt_cfg)).lower(
        params, opt_state, batch
    ).compile()
    out = hlo_walk.analyze(compiled.as_text())
    out["xla_flops"] = float(compiled.cost_analysis().get("flops", 0.0))
    return out
