"""Records the small device trace with the program's own spans that
``test_bench_spans.py`` reads, and prints what ``spans.py`` makes of it.
Run on a machine with a TPU:

    python3 bench/tests/record_spans_trace.py <out.xplane.pb>

Three bursts of 16 pods on 64 nodes of the ``k8s-5000`` configuration,
scheduled through ``run_scenario`` under a registry built with
``device_trace=True``, inside one ``bench.window`` span. The programs are
compiled by a first, untraced run of the same scenario.
"""
import json
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(out: str) -> None:
    import jax

    import generate
    import harness
    import spans
    import xplane
    from repro.core import telemetry
    from repro.core.telemetry import Telemetry

    cfg = json.loads((BENCH / "configs" / "k8s-5000.json").read_text())
    traffic = dict(json.loads((BENCH / "traffic" / "steady.json")
                              .read_text()), n_bursts=3, burst_size=16)
    seed = 2**31 + 99

    def replay():
        fleet = generate.Fleet(cfg, seed, 64)
        return harness.replay_scenario(cfg, traffic, seed, fleet)

    replay()
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tel = Telemetry(timelines=False, device_trace=True)
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(xplane.WINDOW):
        with telemetry.enabled(tel):
            replay()
    jax.profiler.stop_trace()
    shutil.copy(xplane.find_trace(log_dir), out)
    devices, host = xplane.read_events(out)
    found, modules = spans.read_events(out, tel.span_totals())
    for name, ivs in sorted(found.items()):
        print(name, len(ivs), ivs[:3])
    print(spans.busy_by_program(modules, host))
    print(spans.idle_by_span(devices, host, found))
    print("idle", spans.idle_s(devices, host), xplane.reduce(devices, host))


if __name__ == "__main__":
    main(sys.argv[1])
