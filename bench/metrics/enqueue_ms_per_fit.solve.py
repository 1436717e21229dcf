"""Host milliseconds from a fit's ``solve`` call to the return of its scan
program's dispatch, mean over the window's fits: ``solve.scan`` start +
its ``enqueue_s`` - the enclosing ``solve`` span's start (resolution,
layout check, setup dispatch and the scan's enqueue)."""
from bench.spans import ancestor, whole_fits


def read(run):
    scans = whole_fits(run)
    if scans is None:
        return None
    by_id = {ev.get("id"): ev for ev in run.spans if ev.get("ev") == "span"}
    took = []
    for scan in scans:
        enqueue = scan.get("attrs", {}).get("enqueue_s")
        call = ancestor(by_id, scan, "solve")
        if enqueue is None or call is None:
            return None
        took.append(scan["ts"] + enqueue - call["ts"])
    return 1e3 * sum(took) / len(took)
