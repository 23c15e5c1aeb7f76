"""Mean seconds per exploration outside stages 2 and 4: trace load,
problem build, stage 1, stage 3 and the report (wall minus the two
program-timed stages)."""


def read(ctx):
    ex = ctx["explorations"]
    if not ex:
        return None
    return sum(e["wall_s"] - e["stage2_s"] - e["stage4_s"] for e in ex) / len(ex)
