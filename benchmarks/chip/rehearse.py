"""Compile each cell's programs at their real shapes for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [--workload NAME ...]

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology that
is described, not attached.  For each cell it compiles the train step (on one
described chip, or on the cell's mesh over the described chips) and the
reference's step on one chip, and prints ``memory_analysis()`` per device:
bytes of arguments, outputs, temporaries and their sum.  A compile says
nothing about time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def _gb(n) -> str:
    return f"{n / 1e9:.3f} GB"


def _report(name: str, compiled) -> dict:
    m = compiled.memory_analysis()
    row = {"program": name, "args": m.argument_size_in_bytes, "outputs": m.output_size_in_bytes,
           "temps": m.temp_size_in_bytes, "aliased": m.alias_size_in_bytes}
    row["total"] = row["args"] + row["outputs"] + row["temps"] - row["aliased"]
    print(f"{name}: args {_gb(row['args'])} out {_gb(row['outputs'])} "
          f"temps {_gb(row['temps'])} aliased {_gb(row['aliased'])} "
          f"total {_gb(row['total'])}", flush=True)
    return row


def rehearse(name: str, topo) -> list[dict]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import harness
    from repro.configs import ShapeConfig
    from repro.launch.mesh import make_test_mesh
    from repro.launch.sharded_step import abstract_opt_state, build_sharded_step
    from repro.models import build_model
    from repro.models import params as PM
    from repro.train import AdamWConfig, make_train_step
    from weights import shapes

    cell = harness.load_cell(name)
    c, t = cell.config, cell.traffic
    B, S = t["batch"], t["seq_len"]
    one = SingleDeviceSharding(topo.devices[0])
    ref = harness.reference_module(cell)
    cfg_obj = harness.model_config(cell)
    opt_cfg = AdamWConfig(**t["optimizer"])
    rows = []
    if c["program"]["mesh"]:
        mesh = make_test_mesh(data=c["program"]["mesh"]["data"],
                              model=c["program"]["mesh"]["model"],
                              devices=topo.devices[:cell.chips])
        st = build_sharded_step(cfg_obj, ShapeConfig(name, S, B, "train"), mesh,
                                opt_cfg)
        compiled = st.jitted.lower(*st.args).compile()
        rows.append(_report(f"{name} train step, mesh {dict(mesh.shape)}", compiled))
    else:
        model = build_model(cfg_obj, mesh=None)
        put = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
        params = jax.tree.map(put, PM.abstract(model.layout(), cfg_obj.dtype))
        opt = jax.tree.map(put, abstract_opt_state(model.layout(), opt_cfg))
        batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one)
                 for k in ("tokens", "labels")}
        step = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0, 1))
        rows.append(_report(f"{name} train step, one chip",
                            step.lower(params, opt, batch).compile()))
    # the reference's step, on one chip
    f32 = lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)
    p = jax.tree.map(f32, shapes(ref.param_spec(c)), is_leaf=lambda x: isinstance(x, tuple))
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one)
    cnt = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    rstep = ref.make_step(c, t["optimizer"])
    rows.append(_report(f"{name} reference step, one chip",
                        rstep.lower(p, p, p, cnt, tok, tok).compile()))
    return rows


def main(argv=None) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    names = args.workload or [w["name"] for w in bench["workloads"]]
    rows = []
    for name in names:
        rows += rehearse(name, topo)
    print(json.dumps(rows))


if __name__ == "__main__":
    main()
