"""Builder's tool (chip only): record the small trace that
``test_trace_reduce.py`` checks the reduction on.

    python3 benchmark/tests/record_trace.py <out.xplane.pb>

Three bursts of a jitted matrix product with host sleeps between them, so
the trace has device work, idle gaps and a host plane, in a few hundred
kilobytes.
"""

import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, __file__.rsplit("/", 2)[0])
import trace_reduce  # noqa: E402


def main(out: str) -> None:
    assert jax.devices()[0].platform == "tpu", "record on the chip"
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    t0 = time.time()
    for _ in range(3):
        for _ in range(4):
            f(x).block_until_ready()
        time.sleep(0.05)
    window = time.time() - t0
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(d)
    shutil.copy(path, out)
    print(f"window_s={window}")
    print(trace_reduce.describe(out))
    print(trace_reduce.reduce_file(out, window))
    shutil.rmtree(d)


if __name__ == "__main__":
    main(sys.argv[1])
