"""Records the small device trace that ``test_xplane.py`` reduces, and
prints its events for a check by hand. Run on a machine with a TPU:

    python3 bench/tests/record_trace.py <out.xplane.pb>

Three rounds of one jitted program, each inside ``bench.round`` and
``bench.score`` spans, with host sleeps between them, all inside one
``bench.window`` span.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    import xplane
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((2048, 2048), jnp.float32)
    f(x).block_until_ready()
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(xplane.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation(xplane.ROUND):
                with jax.profiler.TraceAnnotation(xplane.SCORE):
                    f(x).block_until_ready()
                time.sleep(0.002)
            time.sleep(0.005)
    jax.profiler.stop_trace()
    shutil.copy(xplane.find_trace(log_dir), out)
    devices, spans = xplane.read_events(out)
    for name, ops in devices.items():
        print(name, [(n, s, e) for n, s, e in ops])
    for name, ivs in spans.items():
        print(name, ivs)
    print(xplane.reduce(devices, spans))
    data = jax.profiler.ProfileData.from_file(out)
    for plane in data.planes:
        print("plane", plane.name, [(ln.name, len(list(ln.events)))
                                    for ln in plane.lines])


if __name__ == "__main__":
    main(sys.argv[1])
