"""Stage-4 programs' share of their roofline, in percent.

Work from shapes alone, for one pass: each candidate row of each stage-4
call reads an 8-byte service time, writes an 8-byte departure and a 1-byte
admit per event, so the least time is rows x events x 17 B over the HBM
peak.  The time is the device time of every stage-4 program in the trace
(round 1, gated replays of later fixed-point rounds, the ring engine), so
extra rounds lower the share.  ``PROGRAMS`` maps the tracked engine names
to XLA program names; a rename leaves the metric out."""

import devtrace

PROGRAMS = {"netsim.kernel.round1": "jit__round1_body",
            "netsim.kernel.replay": "jit_netsim_replay_abs_ref",
            "netsim.engine": "jit__verify_engine_impl"}
BYTES_PER_ROW_EVENT = 17


def read(ctx):
    from repro.analysis.retrace import tracked_names

    if not ctx.get("programs") or not ctx["peaks"]:
        return None
    if not set(PROGRAMS) <= set(tracked_names()):
        return None
    seconds = devtrace.seconds_of(ctx["programs"], PROGRAMS.values())
    work = (ctx["traced_work"] or {}).get("stage4", 0)
    if seconds <= 0 or work <= 0:
        return None
    least = work * BYTES_PER_ROW_EVENT / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
