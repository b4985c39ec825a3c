"""Device self time of a traced run by (first phase, last scope token).

    python benchmarks/stage_times.py <trace dir> [--steps N] [--ops]

The program names its ops twice (sphexa_tpu/util/phases.py): a phase,
``sphexa/<phase>``, and inside some phases a stage,
``sphexa/<phase>~<stage>``. ``trace_reduce.py`` reads the FIRST phase of an
op's scope path, and every metric the benchmark had before PR 33 is a sum of
those. This reader keeps that key and adds a second one beside it: the LAST
``sphexa/`` token of the same path, stage included. So the tree solve's block
loop, which reads whole as ``gravity-mac`` by its first scope, splits into
``(gravity-mac, gravity-mac~prepass)``, ``(gravity-mac, gravity-m2p)``,
``(gravity-mac, gravity-p2p~leaf-ranges)`` ..., and what is left under
``(gravity-mac, gravity-mac)`` is the loop's own carry and slicing; the
exchange layer's functions, shared by the SPH halo and the gravity near
field, read ``(halo-exchange, halo-exchange~wire)`` in the one and
``(gravity-exchange, halo-exchange~wire)`` in the other. Summed over the last
token, the rows of a first phase are ``trace_reduce``'s ``phase_s`` of that
phase and device: the same events, the same clip, the same self times.

One capture is parsed once per process (``of_run``): every layer reader of a
traced run shares the table. STAGES.md has the stages, the metrics and what
the reader cannot mend (a fusion carries one scope; an asynchronous
collective's time is the core's, not the link's).
"""

import os
import re
import sys
import time

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
#: a scope token: ``<phase>`` or ``<phase>~<stage>``; the stage's separator
#: lies outside ``trace_reduce.PHASE_RE``'s class, so the first phase of a
#: path is the same with and without stages
TOKEN_RE = re.compile(trace_reduce.PHASE_RE.pattern
                      + r"(~[A-Za-z0-9_.:+-]+)?")
#: {cell name: table}: the one parse of a process
TABLES = {}
#: ops under no ``sphexa/`` scope, their own or an enclosing op's
UNSCOPED = "(no scope)"


def stage_of(token):
    """``wire`` of ``halo-exchange~wire``; None of a bare phase."""
    return token.partition("~")[2] or None


def _scope_path(events, i, parent):
    """The scope path that names event ``i``: its own where that holds a
    ``sphexa/`` scope, else its nearest enclosing op's (``trace_reduce._phase``
    walks the same way)."""
    while i is not None:
        if trace_reduce.PHASE_RE.search(events[i][3]):
            return events[i][3]
        i = parent[i]
    return None


def table_of_capture(capture, steps=None, with_ops=False, window=None):
    """``{"steps", "window_s", "devices": {ordinal: {"rows": {(first, last):
    ns}, "phase_ns": {first: ns}[, "ops": {(first, last, op name): ns}]}}}``
    of the traced stretch, clipped as ``trace_reduce.reduce_capture`` clips
    it, or of ``window`` = (start, end) on the capture's clock. None where
    the capture holds no device event."""
    traced = [a for a in capture["annotations"] if a[0] == trace_reduce.TRACED]
    if window:
        w0, w1 = window
    elif traced:
        w0, w1 = traced[0][1], traced[0][1] + traced[0][2]
    else:
        spans = [(e[1], e[1] + e[2])
                 for ev in capture["devices"].values() for e in ev]
        if not spans:
            return None
        w0, w1 = min(s for s, _ in spans), max(e for _, e in spans)
    devices = {}
    for ordinal, raw in capture["devices"].items():
        events = trace_reduce._clip(raw, w0, w1)
        if not events:
            continue
        self_ns, parent = trace_reduce._self_times(events)
        rows, phase_ns, ops, unscoped = {}, {}, {}, 0.0
        for i, ev in enumerate(events):
            path = _scope_path(events, i, parent)
            t = max(self_ns[i], 0.0)
            if path is None:
                # what trace_reduce's coverage leaves out: kept by op only
                key = (UNSCOPED, UNSCOPED)
                unscoped += t
            else:
                tokens = [a + b for a, b in TOKEN_RE.findall(path)]
                key = (trace_reduce.PHASE_RE.search(path).group(1),
                       tokens[-1])
                rows[key] = rows.get(key, 0.0) + t
                phase_ns[key[0]] = phase_ns.get(key[0], 0.0) + t
            if with_ops:
                ops[key + (ev[0],)] = ops.get(key + (ev[0],), 0.0) + t
        devices[ordinal] = {"rows": rows, "phase_ns": phase_ns,
                            "unscoped_ns": unscoped}
        if with_ops:
            devices[ordinal]["ops"] = ops
    if not devices:
        return None
    return {"steps": steps, "window_s": (w1 - w0) * 1e-9, "devices": devices}


def of_run(run):
    """The table of a run's capture (``benchmarks/out/<cell>/trace``, where
    ``run.py`` left it), parsed once per process; None for an untraced run."""
    if not run.get("trace"):
        return None
    cell = run["cell"]
    if cell not in TABLES:
        t0 = time.perf_counter()
        capture = trace_reduce.load_capture(
            os.path.join(HERE, "out", cell, "trace"))
        table = table_of_capture(capture, steps=run["trace"]["steps"])
        if table is not None:
            table["parse_s"] = time.perf_counter() - t0
            print(f"# stage_times: parsed {cell}'s capture in "
                  f"{table['parse_s']:.1f} s", flush=True)
        TABLES[cell] = table
    return TABLES[cell]


def ms_per_step(run, first=None, last=None, stages=None, but_stages=None):
    """Self time per traced step, in ms, of the rows selected, on the device
    where they sum highest. A row is selected by its first phase (``first``),
    its whole last token (``last``), its last token's stage (one of
    ``stages``) or by NOT being one of ``but_stages``; what is not given
    selects everything. None without a trace or traced steps, and where no
    device has such a row (a program without the stage). With ``but_stages``
    also where ``first`` has no row of those stages: the whole phase would
    read as the rest."""
    table = of_run(run)
    if not table or not table["steps"]:
        return None

    def keep(f, l, want, unwanted):
        return ((first is None or f == first) and (last is None or l == last)
                and (want is None or stage_of(l) in want)
                and (unwanted is None or stage_of(l) not in unwanted))

    def highest(want, unwanted):
        sums = [[ns for (f, l), ns in d["rows"].items()
                 if keep(f, l, want, unwanted)]
                for d in table["devices"].values()]
        return max(sum(s) for s in sums) if any(sums) else None

    if but_stages is not None and highest(but_stages, None) is None:
        return None
    ns = highest(stages, but_stages)
    return None if ns is None else 1e-6 * ns / table["steps"]


def imbalance(run, phases):
    """Max over min, over the devices, of self time under the first phases
    ``phases``; None without a trace, on one device, or where a device has
    none."""
    table = of_run(run)
    if not table or len(table["devices"]) < 2:
        return None
    totals = [sum(d["phase_ns"].get(p, 0.0) for p in phases)
              for d in table["devices"].values()]
    if min(totals) <= 0.0:
        return None
    return max(totals) / min(totals)


def print_table(table, out=sys.stdout, floor_ms=1.0, ops=0):
    """The whole table, ms per step (ms in all where the steps are not
    known), slowest device by the rows' sum; rows of ``floor_ms`` or more.
    ``ops``: that many device ops under each row, too."""
    per = 1e-6 / (table["steps"] or 1)
    unit = "ms per step" if table["steps"] else "ms"
    worst = max(table["devices"],
                key=lambda k: sum(table["devices"][k]["rows"].values()))
    d = table["devices"][worst]
    print(f"# device {worst} of {sorted(table['devices'])}, {unit}, "
          f"steps {table['steps']}, window {table['window_s']:.3f} s",
          file=out)
    # print-only view: the unscoped time as one more phase with one row
    phases = {k: {**t["phase_ns"], UNSCOPED: t["unscoped_ns"]}
              for k, t in table["devices"].items()}
    all_rows = {**d["rows"], (UNSCOPED, UNSCOPED): d["unscoped_ns"]}
    for first in sorted(phases[worst], key=lambda p: -phases[worst][p]):
        total = phases[worst][first] * per
        if total < floor_ms:
            continue
        others = [ph.get(first, 0.0) * per for ph in phases.values()]
        print(f"{total:10.2f}  {first}   (devices {min(others):.2f}-"
              f"{max(others):.2f})", file=out)
        rows = sorted(((ns * per, last) for (f, last), ns in all_rows.items()
                       if f == first), reverse=True)
        for ms, last in rows:
            if ms < floor_ms:
                continue
            print(f"{ms:14.2f}  {last}", file=out)
            named = sorted(((ns * per, name) for (f, l, name), ns
                            in d.get("ops", {}).items()
                            if (f, l) == (first, last)), reverse=True)
            for op_ms, name in named[:ops]:
                if op_ms >= floor_ms:
                    print(f"{op_ms:22.2f}  {name}", file=out)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--steps", type=int, default=None,
                    help="simulation steps inside the traced stretch")
    ap.add_argument("--ops", type=int, default=0,
                    help="device ops to name under each row")
    ap.add_argument("--floor-ms", type=float, default=1.0)
    args = ap.parse_args()
    t0 = time.perf_counter()
    cap = trace_reduce.load_capture(args.trace_dir)
    print(f"# parsed in {time.perf_counter() - t0:.1f} s")
    print_table(table_of_capture(cap, args.steps, with_ops=bool(args.ops)),
                floor_ms=args.floor_ms, ops=args.ops)
