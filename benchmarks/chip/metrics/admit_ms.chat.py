"""Device milliseconds of admission per admitted request, in a serving
cell: the time of the decode session's prefill programs (``jit_admit``,
one per request, and ``jit_admit_many``, one per batch) over the
requests admitted in the traced window (the runner's ``admissions``
counter; the programs' calls where it is missing)."""

PROGRAMS = ("jit_admit", "jit_admit_many")


def read(ctx):
    progs = [ctx["trace"]["programs"].get(p) for p in PROGRAMS]
    progs = [p for p in progs if p and p["calls"]]
    if not progs:
        return None
    admitted = ctx["counters"].get("admissions") or sum(p["calls"]
                                                        for p in progs)
    return 1e3 * sum(p["device_s"] for p in progs) / admitted
