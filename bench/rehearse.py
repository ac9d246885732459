"""Compile a cell's programs at their real sizes for a described TPU v5e,
with no chip, and print what each would hold in device memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py opt-13b.zo-short [...]

Uses the TPU compiler installed beside JAX and a described ``v5e:2x2``
topology (one of its chips), so a program that would not fit, or a
kernel Mosaic refuses, shows here first.  Nothing runs: the figures are
the compiler's ``memory_analysis()`` of each program, not measurements.
A ZO cell compiles its set-up call (weights and method state), its step
and its parameter-change reading; a serving cell its engine's prefill,
insert, decode and sample executables.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

GIB = 2**30


def _mem(compiled) -> dict:
    ma = compiled.memory_analysis()
    args, out = ma.argument_size_in_bytes, ma.output_size_in_bytes
    temp, alias = ma.temp_size_in_bytes, ma.alias_size_in_bytes
    return {"args_gib": args / GIB, "out_gib": out / GIB,
            "temp_gib": temp / GIB, "alias_gib": alias / GIB,
            "peak_gib": (args + out + temp - alias) / GIB}


def _describe():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import ops

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # Mosaic kernels, and the forward's real kernel path (which asks the
    # backend, a CPU here): steer both as a chip run would take them
    ops.set_interpret(False)
    ops.interpret_forced = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _on(chip, tree):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)


def rehearse_zo(cell, chip) -> dict:
    import jax

    drv = cell.driver
    prog = drv.Program(cell)
    key = jax.ShapeDtypeStruct((2,), "uint32", sharding=chip)
    out = {"init": _mem(prog.init_fn.lower(key, key).compile())}
    state = _on(chip, jax.eval_shape(prog.init_fn, key, key))
    t = cell.traffic
    batch = {k: jax.ShapeDtypeStruct((t["batch"], t["seq"]), "int32",
                                     sharding=chip)
             for k in ("tokens", "targets")}
    out["step"] = _mem(prog.step.lower(state, batch).compile())
    return out


def rehearse_serve(cell, chip) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import ServeEngine
    from repro.models.model import LM

    from bench import weights as W

    t = cell.traffic
    cfg = cell.driver.model_config(cell)
    params = _on(chip, jax.eval_shape(
        lambda k: W.make_params(cell.model, k), jax.random.PRNGKey(0)))
    # the engine's sizes without allocating its page pool here
    orig = LM.init_paged_cache
    LM.init_paged_cache = lambda self, n, ps, abstract=False: orig(
        self, n, ps, abstract=True)
    try:
        e = ServeEngine(cfg, params, max_concurrent_decodes=t["slots"],
                        max_prompt_len=t["max_prompt_len"],
                        max_new_tokens=t["max_new_tokens"],
                        page_size=t["page_size"], eos_id=-1)
    finally:
        LM.init_paged_cache = orig
    cache = _on(chip, e.cache)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)
    out = {"slots": e.n_slots, "pages": int(cache["k"].shape[1]),
           "cache_gib": 2 * cache["k"].size * 2 / GIB}
    for b in e.buckets:
        try:
            pre = jax.jit(e.model.prefill_paged).lower(
                params, i32(1, b), i32()).compile()
            out[f"prefill_{b}"] = _mem(pre)
        except Exception as err:  # noqa: BLE001 - reported, not hidden
            out[f"prefill_{b}"] = "REFUSED: " + str(err).split("\n")[0][:300]
    S, P = e.n_slots, e.pages_per_slot
    dec = jax.jit(e.model.decode_step_paged, donate_argnums=(1,)).lower(
        params, cache, i32(S, P), i32(S), i32(S)).compile()
    out["decode"] = _mem(dec)
    return out


def main(argv=None) -> int:
    from bench import cell as cells

    chip = _describe()
    for name in argv if argv is not None else sys.argv[1:]:
        cell = cells.load(name)
        kind = cell.traffic["driver"]
        fn = {"zo": rehearse_zo, "serve": rehearse_serve}.get(kind)
        if fn is None:
            print(json.dumps({"cell": name, "skipped": kind}))
            continue
        print(json.dumps({"cell": name, **fn(cell, chip)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
