#!/usr/bin/env python3
"""Run a list of benchmark runs one after the other, each a process of its
own (this parent never touches jax, so each child gets the chip), and keep
their result lines and step series.  The tool of the spread study and of
the sets of runs that set the bounds:

    python3 benchmarks/study.py <tag> <workload>:<seed>:<seconds>:<trace> ...

writes ``chiprun_out/<tag>.jsonl`` (one line a run: the arguments, the exit
code, the wall seconds, the result line) and copies ``benchmarks/out/series``
to ``chiprun_out/series``.  ``--summary`` prints per workload and metric
the values, the median and the spread (inter-quartile distance over the
median, quartiles of ``statistics.quantiles(n=4)``)."""

import json
import os
import shutil
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv):
    tag, specs = argv[0], argv[1:]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    with open(os.path.join(out_dir, f"{tag}.jsonl"), "w") as f:
        for spec in specs:
            workload, seed, seconds, trace = spec.split(":")
            cmd = [sys.executable, os.path.join(HERE, "run.py"),
                   "--workload", workload, "--seed", seed,
                   "--seconds", seconds, "--trace", trace]
            t0 = time.time()
            p = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT)
            lines = p.stdout.strip().splitlines()
            try:
                result = json.loads(lines[-1]) if lines else None
            except ValueError:
                result = None
            row = {"spec": spec, "rc": p.returncode,
                   "wall_s": round(time.time() - t0, 1), "result": result}
            rows.append(row)
            f.write(json.dumps(row) + "\n")
            f.flush()
            keep = [ln for ln in p.stderr.splitlines()
                    if ln.startswith(("[", "{")) or "Error" in ln
                    or "Traceback" in ln]
            print(f"== {spec} rc={p.returncode} wall={row['wall_s']}s")
            print("\n".join(keep[-12:]) if p.returncode == 0 and result
                  else p.stderr[-6000:])
            print(lines[-1][:3000] if lines else "(no result line)", flush=True)
    series = os.path.join(HERE, "out", "series")
    if os.path.isdir(series):
        shutil.copytree(series, os.path.join(out_dir, "series"),
                        dirs_exist_ok=True)
    summary(rows)
    return 0


def summary(rows):
    by = {}
    for r in rows:
        if not r["result"]:
            continue
        workload, _seed, _s, trace = r["spec"].split(":")
        for name, m in r["result"]["metrics"].items():
            by.setdefault((workload, trace, name), []).append(m["value"])
    print("== summary (first run of a workload compiles: look at it apart)")
    for (workload, trace, name), vals in sorted(by.items()):
        line = f"{workload} t{trace} {name}: " + " ".join(f"{v:.6g}" for v in vals)
        if len(vals) >= 3:
            q1, _, q3 = statistics.quantiles(vals, n=4)
            med = statistics.median(vals)
            line += f" | median {med:.6g}"
            if med:  # a count that is 0 in every run has no spread
                line += f" spread {(q3 - q1) / med:.4f}"
        print(line)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
