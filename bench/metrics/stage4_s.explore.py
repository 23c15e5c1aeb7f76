"""Mean seconds per exploration in stage 4 (the batched verifier), from
``ScenarioReport.stage4_time_s``."""


def read(ctx):
    ex = ctx["explorations"]
    if not ex:
        return None
    return sum(e["stage4_s"] for e in ex) / len(ex)
