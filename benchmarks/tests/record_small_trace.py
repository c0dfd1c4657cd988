"""Records `data/small_v5e.xplane.pb` on a machine with a TPU (run by hand:
`python benchmarks/tests/record_small_trace.py`, then copy the file)."""

import contextlib
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    assert jax.devices()[0].platform == "tpu", jax.devices()

    @jax.jit
    def small_step(x):
        for _ in range(4):
            x = jnp.tanh(x @ x) * 0.5
        return x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    small_step(x).block_until_ready()
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        HERE, "data", "small_v5e.xplane.pb")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench_window"):
            for i in range(5):
                with (jax.profiler.TraceAnnotation("data_wait") if i
                      else contextlib.nullcontext()):
                    if i:
                        time.sleep(0.02)
                x = small_step(x)
                x.block_until_ready()
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))
        shutil.copy(found[0], out)
    print(out, os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
