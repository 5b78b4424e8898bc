"""Shared scaffolding for the sweep benchmarks.

Every sweep (scenario / carbon / autoscale / scheduling) repeats the same
boilerplate: an argparse front-end with smoke/backend/fleet flags, a
comma-list parser, the ``--backend all`` resolution, the nested
(profile x nodes x variant x backend) cell loop, and the JSON report emit.
This module holds one copy of each; the sweep modules keep only their
cell logic and defaults.
"""
from __future__ import annotations

import argparse
import datetime
import itertools
import json
import os
import platform
import subprocess
from typing import Iterable, Iterator, Sequence

# Append-only JSONL trajectory of recorded sweeps and check verdicts —
# one line per event, so the bench history is a series, not a snapshot.
HISTORY_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "history")

# The batched backends every sweep defaults to; pallas is opt-in
# (interpret mode is slow on CPU).
DEFAULT_BACKENDS = ("numpy", "jax")

# The CI smoke lane's scenario sizes: tiny fleet, few events, whole path
# exercised in seconds.
SMOKE_NODE_COUNTS = (8,)
SMOKE_N_BURSTS = 3
SMOKE_BURST_SIZE = 4


def split_csv(value: str) -> tuple[str, ...]:
    """``"a,b,"`` -> ``("a", "b")`` (empty items dropped)."""
    return tuple(x for x in value.split(",") if x)


def split_csv_int(value: str) -> tuple[int, ...]:
    return tuple(int(x) for x in value.split(",") if x)


def resolve_backends(arg: str,
                     default: Sequence[str] = DEFAULT_BACKENDS
                     ) -> tuple[str, ...]:
    """``--backend all`` -> the sweep's defaults; otherwise a comma-list
    from numpy,jax,pallas."""
    return tuple(default) if arg == "all" else split_csv(arg)


def sweep_parser(out_default: str, profiles: Sequence[str],
                 node_counts: Sequence[int],
                 schemes: Sequence[str] | None = None,
                 policies: Sequence[str] | None = None,
                 backends: Sequence[str] = DEFAULT_BACKENDS
                 ) -> argparse.ArgumentParser:
    """The flag set the scenario-style sweeps share; ``schemes`` /
    ``policies`` add the sweep's variant axis when given."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fleet, few events (CI lane); other flags "
                         "still apply, only the scenario sizes shrink")
    ap.add_argument("--backend", default="all",
                    help=f"all (= {','.join(backends)}; pallas is "
                         "opt-in, interpret mode is slow on CPU) or a "
                         "comma-list from numpy,jax,pallas")
    ap.add_argument("--profiles", default=",".join(profiles))
    ap.add_argument("--nodes", default=",".join(map(str, node_counts)))
    if schemes is not None:
        ap.add_argument("--schemes", default=",".join(schemes))
    if policies is not None:
        ap.add_argument("--policies", default=",".join(policies))
    ap.add_argument("--bursts", type=int, default=8)
    ap.add_argument("--burst-size", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=out_default)
    return ap


def sweep_sizes(args: argparse.Namespace) -> dict:
    """Resolve the scenario sizes from parsed args: the smoke lane's tiny
    sizes, or the flag values."""
    if args.smoke:
        return dict(node_counts=SMOKE_NODE_COUNTS,
                    n_bursts=SMOKE_N_BURSTS, burst_size=SMOKE_BURST_SIZE)
    return dict(node_counts=split_csv_int(args.nodes),
                n_bursts=args.bursts, burst_size=args.burst_size)


def iter_cells(profiles: Iterable, node_counts: Iterable,
               variants: Iterable, backends: Iterable
               ) -> Iterator[tuple]:
    """The sweeps' shared (profile x nodes x variant x backend) cell
    order: backends innermost, so per-(profile, nodes) work (fleet
    construction, verification rows) amortizes naturally."""
    return itertools.product(profiles, node_counts, variants, backends)


def _git_sha() -> str | None:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, check=True).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        return None


def provenance() -> dict:
    """Environment fingerprint for a recorded report: without it a
    BENCH_*.json number is unattributable — was it CPU interpret-mode
    pallas or a real TPU, which jax, which commit, when?"""
    prov: dict = {
        "platform": platform.platform(),
        "python": platform.python_version(),
        "git_sha": _git_sha(),
        "utc_timestamp": datetime.datetime.now(datetime.timezone.utc)
                                 .isoformat(timespec="seconds"),
    }
    try:
        import jax
        from repro.kernels.ops import resolve_interpret
        prov["jax_version"] = jax.__version__
        prov["jax_platform"] = jax.default_backend()
        # the default the pallas wrappers resolve `interpret=None` to
        prov["pallas_interpret"] = resolve_interpret()
    except Exception as e:               # jax broken/absent: record why
        prov["jax_version"] = None
        prov["jax_error"] = repr(e)
    return prov


def write_report(report: dict, out: str | None,
                 history: bool = True) -> dict:
    """Emit a sweep's JSON report with a :func:`provenance` block stamped
    in (no-op when ``out`` is falsy; an explicit block in ``report`` is
    kept), and append the run to the sweep's ``benchmarks/history/``
    JSONL so successive recordings form a trajectory."""
    report.setdefault("provenance", provenance())
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {out}")
        if history:
            from repro.telemetry.baseline import append_history
            bench = report.get("bench") or os.path.basename(out)
            append_history(
                {"kind": "record", "bench": bench,
                 "provenance": report.get("provenance"),
                 "config": report.get("config"),
                 "results": report.get("results")},
                os.path.join(HISTORY_DIR, f"{bench}.jsonl"))
    return report
