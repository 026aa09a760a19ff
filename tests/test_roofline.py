"""Roofline plane: HLO walkers, kernel cost estimates, calibration table.

Covers the satellite of ISSUE 10: ``roofline/analysis.py`` and
``roofline/hlo_walk.py`` had no tests of their own — dtype-byte parsing,
while-body trip-count multiplication and the collective-bytes sum are
asserted here on canned HLO text, alongside the analytic table cells'
determinism contract against the committed
``bench-artifacts/calibration_table.json``.
"""

import json

import pytest

from repro.configs import ARCHS, SHAPES
from repro.roofline.cost import (
    ZERO_COST,
    KernelCost,
    avg_context,
    flash_attention_cost,
    mlstm_scan_cost,
    ssd_scan_cost,
    swiglu_cost,
)
from repro.roofline import analysis, hlo_walk
from repro.roofline.table import (
    DEFAULT_TABLE_PATH,
    analytic_cell,
    cell_key,
    generate_table,
    mesh_dims,
    table_digest,
    table_json,
)

# Canned post-partitioning HLO: a scan-over-layers while loop (24 trips)
# whose body all-reduces a bf16[128,256] gradient, plus an entry-level
# all-gather and a dot.  Tuple-typed computation headers exercise the
# nested-paren header parsing both walkers must survive.
CANNED_HLO = """\
HloModule canned_train_step

%body (p: (s32[], bf16[128,256])) -> (s32[], bf16[128,256]) {
  %p = (s32[], bf16[128,256]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = bf16[128,256] get-tuple-element(%p), index=1
  %ar = bf16[128,256] all-reduce(%x), replica_groups={}
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], bf16[128,256]) tuple(%ni, %ar)
}

%cond (p: (s32[], bf16[128,256])) -> pred[] {
  %p = (s32[], bf16[128,256]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(24)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: bf16[128,256], b: bf16[256,512]) -> bf16[128,256] {
  %a = bf16[128,256] parameter(0)
  %b = bf16[256,512] parameter(1)
  %d = bf16[128,512] dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %z = s32[] constant(0)
  %init = (s32[], bf16[128,256]) tuple(%z, %a)
  %w = (s32[], bf16[128,256]) while(%init), condition=%cond, body=%body
  %ag = bf16[256,256] all-gather(%a), dimensions={0}
  ROOT %r = bf16[128,256] get-tuple-element(%w), index=1
}
"""

AR_BYTES = 128 * 256 * 2          # bf16[128,256]
AG_BYTES = 256 * 256 * 2          # bf16[256,256]
TRIPS = 24


# ---------------------------------------------------------------- analysis.py

def test_type_bytes_dtype_parsing():
    assert analysis._type_bytes("bf16[128,256]") == AR_BYTES
    assert analysis._type_bytes("f32[10]") == 40
    assert analysis._type_bytes("pred[]") == 1
    # tuple types sum their leaves (scalar s32[] + bf16[4,4])
    assert analysis._type_bytes("(s32[], bf16[4,4])") == 4 + 32
    assert analysis._type_bytes("no types here") == 0


def test_split_computations_handles_tuple_headers():
    comps = analysis._split_computations(CANNED_HLO)
    assert set(comps) == {"body", "cond", "main"}
    assert any("all-reduce" in ln for ln in comps["body"])


def test_while_trip_count_multiplies_collective_bytes():
    out = analysis.collective_bytes(CANNED_HLO)
    assert out["all-reduce"] == AR_BYTES * TRIPS
    assert out["all-gather"] == AG_BYTES
    assert out["total"] == AR_BYTES * TRIPS + AG_BYTES


def test_while_multipliers_nested_resolution():
    comps = analysis._split_computations(CANNED_HLO)
    mult = analysis._while_multipliers(comps)
    assert mult["body"] == TRIPS
    assert mult["main"] == 1


# ---------------------------------------------------------------- hlo_walk.py

def test_hlo_walk_parse_and_multipliers():
    comps = hlo_walk.parse_computations(CANNED_HLO)
    assert set(comps) == {"body", "cond", "main"}
    mult = hlo_walk.multipliers(comps)
    assert mult["main"] == 1.0
    assert mult["body"] == TRIPS
    assert mult["cond"] == TRIPS + 1      # one extra evaluation to exit


def test_hlo_walk_analyze_canned():
    out = hlo_walk.analyze(CANNED_HLO)
    # dot: 2*M*N*K = 2 * (128*512) * 256
    assert out["flops"] == 2.0 * 128 * 512 * 256
    assert out["collectives"]["all-reduce"] == AR_BYTES * TRIPS
    assert out["collectives"]["all-gather"] == AG_BYTES
    assert out["collective_total"] == AR_BYTES * TRIPS + AG_BYTES
    assert out["n_computations"] == 3
    assert out["traffic_bytes"] > 0


def test_hlo_walk_known_trip_count_overrides_cond():
    hlo = CANNED_HLO.replace(
        "condition=%cond, body=%body",
        'condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"12"}}',
    )
    comps = hlo_walk.parse_computations(hlo)
    assert hlo_walk.multipliers(comps)["body"] == 12


# ------------------------------------------------------------- roofline/cost.py

def test_avg_context_causal_and_windowed():
    assert avg_context(64, 64) == pytest.approx((64 + 1) / 2)
    # sliding window w over S keys: exact mean w - w(w-1)/(2S)
    assert avg_context(64, 64, window=8) == pytest.approx(8 - 8 * 7 / (2 * 64))
    # a window wider than the sequence degenerates to causal
    assert avg_context(64, 64, window=1024) == avg_context(64, 64)
    assert avg_context(64, 64, causal=False) == 64


def test_flash_attention_cost_flops():
    b, h, s, hd = 2, 4, 64, 32
    kc = flash_attention_cost(b, h, s, s, hd, causal=True)
    assert kc.flops == pytest.approx(4.0 * b * h * s * avg_context(s, s) * hd)
    assert kc.bytes_accessed > 0
    # windowed attention visits fewer keys -> strictly cheaper
    kw = flash_attention_cost(b, h, s, s, hd, causal=True, window=8)
    assert kw.flops < kc.flops


def test_kernel_cost_algebra():
    a = KernelCost(flops=10.0, bytes_accessed=4.0, transcendentals=1.0)
    b = KernelCost(flops=5.0, bytes_accessed=2.0)
    assert (a + b).flops == 15.0
    assert a.scale(3).bytes_accessed == 12.0
    assert (ZERO_COST + a) == a


def test_scan_kernel_costs_scale_with_length():
    short = mlstm_scan_cost(2, 4, 64, 16, 32)
    long = mlstm_scan_cost(2, 4, 128, 16, 32)
    assert long.flops > short.flops
    s1 = ssd_scan_cost(2, 4, 64, 32, 16)
    s2 = ssd_scan_cost(2, 4, 128, 32, 16)
    assert s2.flops > s1.flops
    assert swiglu_cost(128, 64, 256).flops == pytest.approx(6.0 * 128 * 64 * 256)


# ------------------------------------------------------------------- table.py

def test_mesh_dims():
    assert mesh_dims("64x4") == (64, 4)
    for bad in ("foo", "4", "0x4", "4x0", "axb"):
        with pytest.raises(ValueError):
            mesh_dims(bad)


def test_analytic_cell_terms_no_jax():
    cfg = ARCHS["qwen1.5-0.5b"]
    shape = SHAPES["train_4k"]
    r = analytic_cell(cfg, shape, "64x4", n_params=464_000_000)
    assert r.chips == 256
    assert r.step_time_s == max(r.compute_s, r.memory_s, r.collective_s)
    assert r.bottleneck in ("compute", "memory", "collective")
    assert 0 < r.mfu < 1
    # deterministic: the same cell prices identically every time
    assert r.to_dict() == analytic_cell(cfg, shape, "64x4", n_params=464_000_000).to_dict()
    # widening the model axis moves bytes per chip down, collectives up
    r2 = analytic_cell(cfg, shape, "4x16", n_params=464_000_000)
    assert r2.collectives["tp-all-reduce"] > r.collectives["tp-all-reduce"]


def test_committed_table_cells_regenerate_identically():
    """Determinism contract: regeneration reproduces the committed cells."""
    committed = json.loads(DEFAULT_TABLE_PATH.read_text())
    archs = ["hymba-1.5b", "qwen1.5-0.5b"]        # one attention, one hybrid
    fresh = generate_table(archs=archs)
    assert fresh["hardware"] == committed["hardware"]
    for key, cell in fresh["cells"].items():
        assert committed["cells"][key] == cell, f"cell {key} drifted"
    # the canonical byte form is itself stable across regenerations
    again = generate_table(archs=archs)
    assert table_json(fresh) == table_json(again)
    assert table_digest(fresh) == table_digest(again)
    # every committed cell honours step = max(compute, memory, collective)
    for key, cell in committed["cells"].items():
        assert cell["step_time_s"] == max(
            cell["compute_s"], cell["memory_s"], cell["collective_s"]
        ), key
    assert cell_key("a", "s", "1x1") == "a|s|1x1"
