"""How ``small_trace.xplane.pb`` was recorded (on the TPU v5e, PR 24):

    chiprun -- python3 benchmarks/fixtures/record_fixture.py chiprun_out/fixture

Three executions of one small jitted program (a chain of matmuls inside a
fori_loop) with a host sleep between them, under the benchmark's window
mark and a host span, so that the reduction has modules, nested
operations, busy time and idle gaps to find. The test that reads it is
tests/bench_harness/test_benchmarks.py.
"""
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks.trace_reduce import WINDOW_MARK, reduce_trace

    @jax.jit
    def fixture_step(x):
        return jax.lax.fori_loop(0, 8, lambda _i, a: jnp.tanh(a @ a) * 0.5, x)

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    fixture_step(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    time.sleep(0.2)   # the device tracer arms a little after the call
    with jax.profiler.TraceAnnotation(WINDOW_MARK):
        time.sleep(0.01)   # the device's clock runs ~1 ms ahead of the host's
        for _ in range(3):
            fixture_step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench:fixture_sleep"):
                time.sleep(0.02)
    time.sleep(0.01)
    jax.profiler.stop_trace()
    print(reduce_trace(out_dir))


if __name__ == "__main__":
    main(sys.argv[1])
