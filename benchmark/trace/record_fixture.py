"""Record the small trace the reduction is checked on
(``tests/data/small.xplane.pb``): two named programs run a few times with
host annotations between them.  Run on the chip:

    chiprun -- python -m benchmark.trace.record_fixture chiprun_out/fixture

and copy the ``*.xplane.pb`` it leaves to ``benchmark/tests/data/``.
"""
import glob
import json
import os
import shutil
import sys
import time


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fixture_big(x):
        return jax.lax.fori_loop(0, 8, lambda _, a: (a @ a) * 1e-3, x)

    @jax.jit
    def fixture_small(x):
        return jnp.tanh(x) + 1.0

    x = jnp.full((1024, 1024), 1e-2, jnp.bfloat16)
    jax.block_until_ready((fixture_big(x), fixture_small(x)))
    tmp = os.path.join(out, "raw")
    shutil.rmtree(tmp, ignore_errors=True)
    t0 = time.perf_counter()
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench:step_dispatch"):
            y = fixture_big(x)
        with jax.profiler.TraceAnnotation("bench:device_block"):
            y.block_until_ready()
        with jax.profiler.TraceAnnotation("bench:data_wait"):
            time.sleep(0.005)
        fixture_small(x).block_until_ready()
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    from benchmark.trace import xplane

    from jax.profiler import ProfileData
    data = ProfileData.from_file(os.path.join(out, "small.xplane.pb"))
    for plane in data.planes:
        print("plane", plane.name, [(l.name, len(list(l.events))) for l in plane.lines][:12])
    summ = xplane.summarize(xplane.load(os.path.join(out, "small.xplane.pb")), window)
    summ["op_self_s"] = dict(sorted(summ["op_self_s"].items(), key=lambda kv: -kv[1])[:20])
    print(json.dumps(summ, indent=1))
    print("bytes", os.path.getsize(os.path.join(out, "small.xplane.pb")))


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
