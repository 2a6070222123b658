"""Records `scoped_small.xplane.pb`, the trace `test_yardstick_scopes.py`
pins the stage-scope reduction on: three calls of one jitted program
inside `bench.window`, with a matmul under `jax.named_scope
("fixture.matmul")`, a `lax.while_loop` whose body sits under
`jax.named_scope("fixture.loop")`, and an unscoped add after the loop.

    python3 tests/benchmark/fixtures/make_scoped_fixture.py <out dir>

Run it on a TPU: a CPU trace has no "XLA Ops" device line.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax import lax


@jax.jit
def program(x):
    with jax.named_scope("fixture.matmul"):
        y = x @ x

    def body(carry):
        i, v = carry
        with jax.named_scope("fixture.loop"):
            v = jnp.tanh(v @ y)
        return i + 1, v

    _, v = lax.while_loop(lambda c: c[0] < 4, body, (0, y))
    return v + 1.0


def main(out: str) -> None:
    x = jnp.ones((256, 256), jnp.float32) / 256.0
    jax.block_until_ready(program(x))  # compiled outside the trace
    logdir = tempfile.mkdtemp()
    jax.profiler.start_trace(logdir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                jax.block_until_ready(program(x))
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    os.makedirs(out, exist_ok=True)
    shutil.copy(path, os.path.join(out, "scoped_small.xplane.pb"))


if __name__ == "__main__":
    main(sys.argv[1])
