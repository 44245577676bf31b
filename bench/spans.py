"""The program's own spans in a profiler trace: self times, and what the
host was doing in each idle gap.

The program spans every stage of its served path as
``jax.profiler.TraceAnnotation``s named ``repro/<layer>/<stage>``, on the
profiler's clock beside the harness's ``bench/...`` spans.  ``load`` reads
them as ``(name, start_ns, end_ns, thread, args)``; ``self_times`` gives each
name's time less that of the spans directly inside it; and
``bench.trace.longest_gaps``, handed both kinds of span, puts each idle gap
under the innermost one that covers it.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

runs the cell as ``bench/run.py --trace 1`` does, with its idle gaps put
under program spans too, and prints after its result line one more JSON
line: the program's self times in the traced window, per request closed
there, and how much of the ``bench/flush`` time the program's spans cover.
"""
from __future__ import annotations

import glob
import json
import os
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
PREFIX = "repro/"
CLOSE_SPAN = "repro/serve/close"


def load(profile_dir: str) -> list:
    """``(name, start_ns, end_ns, thread, args)`` of every program span in
    the newest trace under ``profile_dir``; ``thread`` tells host lines
    apart, ``args`` are the span's annotation arguments."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return [(e.name, e.start_ns, e.end_ns, (plane.name, i),
             {k: v for k, v in e.stats})
            for plane in data.planes for i, ln in enumerate(plane.lines)
            for e in ln.events if e.name.startswith(PREFIX)]


def self_times(spans, lo: float, hi: float) -> dict:
    """``{name: {"self_s": s, "n": count}}`` over the spans that overlap
    ``[lo, hi]``: each span's time inside the window less that of the spans
    directly inside it on the same thread.  Spans are ``(name, start, end)``
    or ``(name, start, end, thread, ...)``; spans of one thread nest."""
    threads: dict = defaultdict(list)
    for sp in spans:
        if sp[2] > lo and sp[1] < hi:
            threads[sp[3] if len(sp) > 3 else None].append(sp[:3])
    out: dict = {}
    for evs in threads.values():
        evs.sort(key=lambda x: (x[1], -x[2]))  # parents before children
        stack: list = []
        for name, s, e in evs:
            while stack and stack[-1][2] <= s:
                stack.pop()
            t = (min(e, hi) - max(s, lo)) * 1e-9
            rec = out.setdefault(name, {"self_s": 0.0, "n": 0})
            rec["self_s"] += t
            rec["n"] += 1
            if stack:
                out[stack[-1][0]]["self_s"] -= t
            stack.append((name, s, e))
    return out


def summary(profile_dir: str, chips: int) -> dict:
    """The program's self times in the traced window, the time the program's
    spans cover inside ``bench/flush``, the requests of the batch closes
    that start in the window, and the ten longest idle gaps, each under
    the innermost harness or program span that covers it."""
    from bench import trace as tr

    t = tr.load(profile_dir)
    lo, hi = t["window"]
    prog = load(profile_dir)
    flat = [sp[:3] for sp in prog]
    flush = [sp for sp in tr.clip(t["spans"], lo, hi)
             if sp[0] == "bench/flush"]
    dev = {i: tr.clip(t["device"].get(i, []), lo, hi) for i in range(chips)}
    return {"program": self_times(prog, lo, hi),
            "closed": sum(int(sp[4].get("n", 0)) for sp in prog
                          if sp[0] == CLOSE_SPAN and lo <= sp[1] <= hi),
            "flush_s": sum(e - s for _, s, e in flush) * 1e-9,
            "flush_program_s": sum(tr.busy_ns(flat, s, e)
                                   for _, s, e in flush) * 1e-9,
            "idle_gaps": tr.longest_gaps(dev, t["spans"] + flat, lo, hi)}


def main(argv=None) -> int:
    """One traced run of a cell, with the program's spans read too."""
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import argparse

    from bench import harness, run

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    found: dict = {}
    inner = harness.trace_summary

    def trace_summary(prof, chips, cfg):
        # read the program's spans before the harness removes the trace
        found.update(summary(prof.dir, chips))
        out = inner(prof, chips, cfg)
        out["idle_gaps"] = found["idle_gaps"]
        return out

    harness.trace_summary = trace_summary
    code = run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1"])
    if code or not found:
        return code or 1
    closed, prog = found["closed"], found["program"]
    print(json.dumps({
        "program_us_per_req": {k: v["self_s"] / closed * 1e6
                               for k, v in sorted(prog.items())}
        if closed else None,
        "program": prog, "closed": closed,
        "flush_s": found["flush_s"],
        "flush_program_s": found["flush_program_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
