"""Record the small profiler trace that ``test_xplane.py`` reads.

Run once on a machine with a TPU, from the root of a checkout:

    python3 chipbench/tests/record_trace.py

It times a few small matmuls inside the benchmark's host spans (``tick``,
``wait_arrival``) with idle sleeps between them, writes the trace to
``chipbench/tests/data/``, and prints the planes and lines it holds.
"""

import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[0] = str(HERE.parents[1])


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    from chipbench import xplane

    if jax.devices()[0].platform != "tpu":
        print("error: no TPU", file=sys.stderr)
        return 1
    step = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    step(x).block_until_ready()
    tmp = HERE / "data" / "raw"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(str(tmp))
    for _ in range(4):
        with TraceAnnotation("tick"):
            for _ in range(3):
                x = step(x)
            x.block_until_ready()
        with TraceAnnotation("wait_arrival"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    src = xplane.newest_xplane(tmp)
    dst = HERE / "data" / "small.xplane.pb"
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    for plane in ProfileData.from_file(str(dst)).planes:
        print(plane.name, [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines])
    devices, host = xplane.read_events(dst)
    print({k: len(v) for k, v in devices.items()}, len(host))
    print(f"{dst}: {dst.stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
