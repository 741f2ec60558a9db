"""The sharded cell on four virtual CPU devices, for ``test_sharded.py``.

JAX has to see four devices before it starts, so this runs in a process
of its own, with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
and ``JAX_PLATFORMS=cpu``; the test process keeps its one device. Each
run is cut to ``conftest.TINY`` and skips the look for a chip. Prints one
JSON object: the result lines of a run at ``--trace 0`` and ``--trace 1``,
the results of runs with each planted fault, ``shards_misplaced`` for
code tables placed in three ways, and the control's readings.
"""

import json
import sys
from pathlib import Path

TESTS = Path(__file__).resolve().parent
for p in (TESTS, TESTS.parent, TESTS.parents[1] / "src"):
    sys.path.insert(0, str(p))

CELL = "sift128-sharded4-u8.batch1024"
FAULTS = ("one_shard_dropped", "altered")


def run(trace: int, seed: int):
    import harness
    from conftest import TINY

    args = harness.parser().parse_args(
        ["--workload", CELL, "--seed", str(seed), "--seconds", "1.5",
         "--trace", str(trace)])
    result, _ = harness.run(args, require_chip=False, overrides=TINY)
    return result


def placements() -> dict:
    """``shards_misplaced`` of a (4, 8, 3) table laid out as the engine
    lays it, kept whole on device 0, and copied to every device."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import reference

    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices), ("data",))
    table = np.arange(4 * 8 * 3, dtype=np.uint8).reshape(4, 8, 3)
    put = {"stacked": NamedSharding(mesh, P("data", None, None)),
           "device0": devices[0],
           "everywhere": NamedSharding(mesh, P())}
    return {name: reference.shards_misplaced(
        {"codes": jax.device_put(table, where)}, devices)
        for name, where in put.items()}


def main() -> None:
    import control
    import harness
    import profile_reduce
    from conftest import TINY
    from repro.launch import compile_cache
    from repro.search import engine as eng

    compile_cache.enable = lambda: "off in tests"
    out = {"run": run(0, 2**31 + 11)}
    # on the CPU the operations run on the host's client thread
    profile_reduce.DEVICE_PLANE_PREFIX = "/host:CPU"
    profile_reduce.DEVICE_OP_LINE = "tf_XLA"
    out["traced"] = run(1, 2**31 + 12)
    search = eng.ShardedGraphEngine.search
    out["faults"] = {}
    for name in FAULTS:
        eng.ShardedGraphEngine.search = control.SERVING_FAULTS[name](search)
        try:
            out["faults"][name] = run(0, 5)
        finally:
            eng.ShardedGraphEngine.search = search
    out["placements"] = placements()
    args = harness.SimpleNamespace(workload=CELL, seconds=1.0, study=True,
                                   faults=[])
    out["control"] = list(control.readings(args, [7], require_chip=False,
                                           overrides=TINY))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
