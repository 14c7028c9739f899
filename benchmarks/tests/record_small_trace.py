"""Records ``data/small.xplane.pb`` on the chip (run once, by hand, through
the chip tool; the file it writes to ``chiprun_out/`` is copied here)."""

import glob
import os
import shutil
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def main():
    @jax.jit
    def small_chain(x):
        for _ in range(4):
            x = jnp.tanh(x @ x) * 0.01
        return x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    small_chain(x).block_until_ready()
    log_dir = "chiprun_out/small_trace"
    shutil.rmtree(log_dir, ignore_errors=True)
    jax.profiler.start_trace(log_dir)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.unit"):
                small_chain(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(src, "chiprun_out/small.xplane.pb")
    shutil.rmtree(log_dir, ignore_errors=True)
    print("wrote chiprun_out/small.xplane.pb", os.path.getsize("chiprun_out/small.xplane.pb"), "bytes")


if __name__ == "__main__":
    main()
