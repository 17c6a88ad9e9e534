"""A builder's tool, run once on the chip: record the small trace that
``test_trace_reduce.py`` checks the reduction on.  Five executions of one
small program with the host asleep between them, inside the harness's
window annotation; the result goes to ``chiprun_out/`` and is committed
as ``tests/small_tpu.xplane.pb``."""
import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import trace_reduce  # noqa: E402


def main():
    step = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    step(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("chipbench:step"):
                step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("chipbench:nap"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    os.makedirs("chiprun_out", exist_ok=True)
    shutil.copy(src, "chiprun_out/small_tpu.xplane.pb")
    print(os.path.getsize(src), "bytes")
    r = trace_reduce.reduce_trace(src)
    print({k: r[k] for k in ("window_s", "busy_s")}, r["modules"].keys(),
          r["idle_gaps"][:5])


if __name__ == "__main__":
    main()
