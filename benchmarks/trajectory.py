"""Perf-trajectory report across committed BENCH_*.json artifacts.

The repo's perf history lives in ``results/BENCH_*.json`` (one artifact
per landed optimization, written by ``benchmarks/run.py --json``), but the
trajectory itself was only recorded implicitly in CHANGES.md prose.  This
tool prints it as a table — total/ftl/sim/compile/exec seconds plus the
per-phase wall-clock — ordered by generation time, and writes
``results/TRAJECTORY.md`` (uploaded as a CI artifact).

Ordering: artifacts carry ``generated_at`` since the warm-path PR; older
ones fall back to file mtime, then name (which happens to sort the
pre-existing artifacts in landing order).  Presets are reported in
separate tables — a --smoke probe and a quick run are not comparable.

  PYTHONPATH=src python -m benchmarks.trajectory [--results results]
      [--out results/TRAJECTORY.md]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

PHASE_ORDER = ("fig4_9_10_13", "fig11", "fig12", "fig14", "fig15", "tail",
               "stream", "tab4", "sec31")


def load_artifacts(results_dir: str) -> list:
    arts = []
    for path in glob.glob(os.path.join(results_dir, "BENCH_*.json")):
        try:
            with open(path) as f:
                art = json.load(f)
        except (OSError, ValueError) as e:
            print(f"[trajectory] skipping {path}: {e}")
            continue
        name = os.path.basename(path)
        key = (art.get("generated_at") or "", os.path.getmtime(path), name)
        arts.append((key, name, art))
    arts.sort(key=lambda t: t[0])
    return [(name, art) for _, name, art in arts]


def _fmt(v, nd=1):
    if v is None:
        return "-"
    return f"{float(v):.{nd}f}"


def _kernel_split(art: dict) -> tuple:
    """("batched%", "scout%", "backend") cells from the
    ``kernel_dispatch`` block; pre-PR-7 artifacts lack the block and
    pre-PR-10 ones the scout split — both render as "-"."""
    kd = art.get("kernel_dispatch")
    if not kd:
        return "-", "-", "-"
    share = kd.get("batched_share")
    share_s = "-" if share is None else f"{100.0 * float(share):.0f}%"
    sshare = kd.get("scout_batched_share")
    sshare_s = "-" if sshare is None else f"{100.0 * float(sshare):.0f}%"
    backends = kd.get("backends") or {}
    be_s = ("-" if not backends else
            " ".join(f"{k}:{v}" for k, v in sorted(backends.items())))
    return share_s, sshare_s, be_s


def rows_for(arts: list) -> tuple:
    """(header, rows) of the trajectory table for one preset's artifacts."""
    phases = [p for p in PHASE_ORDER
              if any(p in (a.get("phases") or {}) for _, a in arts)]
    header = (["artifact", "total_s", "ftl_s", "sim_s", "compile_s",
               "exec_s", "cwait_s", "covl_s", "groups", "batched%", "scout%",
               "kernels"]
              + [f"{p}_s" for p in phases])
    rows = []
    for name, art in arts:
        ph = art.get("phases") or {}
        groups = art.get("groups")
        share_s, sshare_s, be_s = _kernel_split(art)
        rows.append(
            [name.replace("BENCH_", "").replace(".json", ""),
             _fmt(art.get("total_s")), _fmt(art.get("ftl_s_total"), 2),
             _fmt(art.get("sim_s_total")),
             _fmt(art.get("compile_s_total"), 2),
             _fmt(art.get("exec_s_total"), 2),
             _fmt(art.get("compile_wait_s"), 2),
             _fmt(art.get("compile_overlap_s"), 2),
             str(len(groups)) if isinstance(groups, list) else "-",
             share_s, sshare_s, be_s]
            + [_fmt((ph.get(p) or {}).get("s")) for p in phases]
        )
    return header, rows


def render(results_dir: str) -> str:
    arts = load_artifacts(results_dir)
    by_preset: dict = {}
    for name, art in arts:
        by_preset.setdefault(art.get("preset") or "?", []).append(
            (name, art))
    lines = ["# Perf trajectory (committed BENCH_*.json artifacts)", ""]
    lines.append("Regenerate: `PYTHONPATH=src python -m "
                 "benchmarks.trajectory`.  Ordering: `generated_at`, then "
                 "file mtime, then name.  Wall-clock fields are seconds "
                 "of CPU host time; `cwait_s`/`covl_s` split "
                 "background compilation into dispatcher stall vs time "
                 "hidden behind execution; `batched%` is the share "
                 "of static lane-steps run by the batched static step, "
                 "`scout%` the share of scout lane-steps run by the batched "
                 "scout runner, and `kernels` the per-backend group counts "
                 "(xla / pallas-interpret / pallas-compiled).")
    for preset in sorted(by_preset):
        header, rows = rows_for(by_preset[preset])
        lines += ["", f"## preset: {preset}", ""]
        lines.append("| " + " | ".join(header) + " |")
        lines.append("|" + "---|" * len(header))
        for r in rows:
            lines.append("| " + " | ".join(r) + " |")
        # streaming-engine acceptance surface: per-window simulated-IOs per
        # wall-clock second of the beyond-budget replay (must stay flat)
        for name, art in by_preset[preset]:
            stream = art.get("stream") or {}
            wins = [w for w in stream.get("windows", [])
                    if w.get("n_requests")]
            if wins:
                per = " ".join(f"w{w['window']}={_fmt(w['ios_per_wallclock_s'], 0)}"
                               for w in wins)
                lines.append(
                    f"- `{name.replace('BENCH_', '').replace('.json', '')}` "
                    f"stream IO/s per window: {per} (flatness "
                    f"{_fmt(stream.get('throughput_flatness'), 2)})")
    return "\n".join(lines) + "\n"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--results", default="results")
    ap.add_argument("--out", default=None,
                    help="markdown output path (default "
                         "<results>/TRAJECTORY.md); '-' = stdout only")
    args = ap.parse_args()
    md = render(args.results)
    print(md)
    out = args.out or os.path.join(args.results, "TRAJECTORY.md")
    if out != "-":
        with open(out, "w") as f:
            f.write(md)
        print(f"[trajectory] written to {out}")


if __name__ == "__main__":
    main()
