"""Mean configs per compiled scan program in the service's drains, from
the program's ``repro.obs`` spans: a ``group.vmap`` or ``group.cohort``
span runs ``size`` configs as lanes of one program, a ``group.sequential``
span runs each of its ``size`` configs as a program of its own."""

LANED = ("group.vmap", "group.cohort")


def read(run):
    configs = scans = 0
    for ev in run.spans:
        if ev.get("ev") != "span":
            continue
        size = int(ev.get("attrs", {}).get("size", 0))
        if ev["name"] in LANED:
            configs, scans = configs + size, scans + 1
        elif ev["name"] == "group.sequential":
            configs, scans = configs + size, scans + size
    return configs / scans if scans else None
