"""Benchmark driver: one section per paper table/figure. CSV: name,us_per_call,derived.

  fig3   communication volume, unified vs zerocopy (paper Fig. 3 analogue)
  fig7   design-scenario speedups on 4 devices      (paper Fig. 7)
  fig8   interconnect sensitivity model             (paper Fig. 8)
  fig9   tasks-per-device sensitivity               (paper Fig. 9)
  fig10  strong scaling 1..8 devices                (paper Fig. 10)
  lm     LM substrate step times (reduced configs)
  roofline  §Roofline terms from dry-run artifacts (if present)

Every section runs in a subprocess of its own (one process per chip); on the
CPU it gets a forced host device count, and on a TPU a section fails when
fewer chips exist than it asks for.
``REPRO_BENCH_SCALE`` scales the Table-I suite (default 0.1);
``REPRO_BENCH_FAST=1`` (or ``--quick``) runs a reduced set for CI-style smoke
runs.

Besides the CSV on stdout, every run writes a machine-readable
``{name: {"us_per_call": float, "derived": str}}`` map of the same rows. The
file name comes from ``REPRO_BENCH_JSON`` when set, else
``BENCH_PR<REPRO_PR_NUMBER>.json``, else ``BENCH.json``. CI uploads it as the
``bench-trajectory`` artifact and ``benchmarks/compare.py`` gates the next
run against it (>25% per-row regressions fail).

  krylov  IC(0)-PCG iteration cost, suite x comm/partition x RHS batch
  auto    session-API auto picks vs fixed backends + context cache hit rate
  service solves/sec at a multi-tenant request mix (batched vs one-by-one)
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import run_with_devices  # noqa: E402


class _Tee(io.TextIOBase):
    """Mirror writes to the real stdout while recording them for the JSON dump."""

    def __init__(self, stream):
        self.stream = stream
        self.buffer_text = io.StringIO()

    def write(self, s: str) -> int:
        self.buffer_text.write(s)
        return self.stream.write(s)

    def flush(self) -> None:
        self.stream.flush()


def provenance() -> dict:
    """Environment fingerprint stored under the ``_provenance`` key of the
    bench JSON: enough to explain a cross-run timing shift (different jax,
    different device fleet, different commit) without gating on it. Called
    after every section's process has exited, so querying the devices here
    takes no chip from them; a failed device query fails the run."""
    import datetime

    import jax

    devs = jax.devices()
    prov = {"timestamp_utc": datetime.datetime.now(
                datetime.timezone.utc).isoformat(timespec="seconds"),
            "jax_version": jax.__version__, "platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "device_count": len(devs),
            "git_sha": ""}
    try:  # a checkout without git history has no sha to record
        import subprocess

        prov["git_sha"] = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)), timeout=10,
        ).stdout.strip()
    except Exception:
        pass
    return prov


def rows_from_csv(text: str) -> dict:
    """Parse ``name,us_per_call,derived`` lines into the JSON row map."""
    rows = {}
    for line in text.splitlines():
        parts = line.strip().split(",", 2)
        if len(parts) < 2 or parts[0] in ("", "name"):
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        rows[parts[0]] = {"us_per_call": us,
                          "derived": parts[2] if len(parts) > 2 else ""}
    return rows


def main() -> None:
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        print("name,us_per_call,derived")
        fast = os.environ.get("REPRO_BENCH_FAST", "0") == "1" or "--quick" in sys.argv[1:]
        scale = os.environ.get("REPRO_BENCH_SCALE", "0.05" if fast else "0.1")
        env = {"REPRO_BENCH_SCALE": scale}

        # plan-level analysis (one device)
        print(run_with_devices("benchmarks.bench_comm_volume", 1, env), end="")
        print(run_with_devices("benchmarks.bench_interconnect_model", 1, env),
              end="")

        # multi-device sections (subprocess with forced device count)
        print(run_with_devices("benchmarks.bench_scenarios", 4, env), end="")
        auto_env = dict(env, REPRO_BENCH_FAST="1" if fast else "0")
        print(run_with_devices("benchmarks.bench_auto", 4, auto_env), end="")
        # serving axis: solves/sec at a request mix (single device; the
        # coalesce-win gate in compare.py keys on these rows in every mode)
        print(run_with_devices("benchmarks.bench_service", 1, env), end="")
        if not fast:
            print(run_with_devices("benchmarks.bench_krylov", 4, env), end="")
            print(run_with_devices("benchmarks.bench_tasks", 4, env), end="")
            print(run_with_devices("benchmarks.bench_scaling", 8, env), end="")
            print(run_with_devices("benchmarks.bench_lm_step", 1, env), end="")

        # roofline table from dry-run artifacts, if the sweep has run
        if os.path.isdir("experiments/dryrun"):
            from benchmarks import roofline

            rows = [r for r in map(roofline.roofline_row, roofline.load_cells()) if r]
            for r in rows:
                name = f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}"
                derived = (
                    f"bottleneck={r['bottleneck']};frac={r['roofline_fraction']:.2f};"
                    f"useful={r['useful_flops_ratio']:.2f}"
                )
                print(f"{name},{r['bound_s']*1e6:.1f},{derived}")

    pr = os.environ.get("REPRO_PR_NUMBER")
    default = f"BENCH_PR{pr}.json" if pr else "BENCH.json"
    out = os.environ.get("REPRO_BENCH_JSON", default)
    blob = rows_from_csv(tee.buffer_text.getvalue())
    # "_"-prefixed keys are metadata, not timing rows: compare.py ignores
    # them for gating and surfaces provenance next to failures
    blob["_provenance"] = provenance()
    with open(out, "w") as f:
        json.dump(blob, f, indent=1, sort_keys=True)
    sys.stderr.write(f"[bench] wrote {out}\n")


if __name__ == "__main__":
    main()
