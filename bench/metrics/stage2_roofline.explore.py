"""Stage-2 programs' share of their roofline, in percent.

Work from shapes alone: each candidate row of each stage-2 call reads an
8-byte service time and writes an 8-byte departure per event, so the least
time is rows x events x 16 B over the HBM peak (bytes bound this scan; it
does no floating-point work worth counting).  The time is the device time
of the stage-2 programs in the profiler trace; both over the traced
window.  ``PROGRAMS`` maps the
engine name the program tracks to its XLA program name, so a rename leaves
the metric out instead of reading 0."""

import devtrace

PROGRAMS = {"surrogate.engine": "jit__engine_impl"}
BYTES_PER_ROW_EVENT = 16


def read(ctx):
    from repro.analysis.retrace import tracked_names

    if not ctx.get("programs") or not ctx["peaks"]:
        return None
    if not set(PROGRAMS) <= set(tracked_names()):
        return None
    seconds = devtrace.seconds_of(ctx["programs"], PROGRAMS.values())
    work = (ctx["traced_work"] or {}).get("stage2", 0)
    if seconds <= 0 or work <= 0:
        return None
    least = work * BYTES_PER_ROW_EVENT / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
