"""Record the small profiler trace the reduction's test reads.

    python bench/tests/record_trace.py [out.xplane.pb]

Needs the chip.  Inside one ``bench.window`` span, after 20 ms: 3
``bench.tick`` spans, each running the jitted ``step`` program twice
(device busy), each followed by a ``bench.wait`` span of 50 ms in which
the device runs nothing.
"""
import glob
import pathlib
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

OUT = pathlib.Path(__file__).resolve().parent / "data" / "tpu_trace.xplane.pb"


def main() -> None:
    if jax.default_backend() != "tpu":
        sys.exit("record_trace: needs a TPU")
    out = pathlib.Path(sys.argv[1]) if len(sys.argv) > 1 else OUT

    @jax.jit
    def step(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.ones((512, 512), jnp.float32) * 0.01
    step(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        time.sleep(0.02)        # room for the device clock's offset
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.tick"):
                x = step(step(x))
                x.block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.05)
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(tmp)
    print(f"{out}: {out.stat().st_size} bytes")


if __name__ == "__main__":
    main()
