NAME = "tree_rows_per_edit"
UNIT = "count"
LAYER = "engine (tree_batch_engine.py)"
MOVES = "applied_ops_per_s"
READS = "status lines, window delta: rows staged over edits translated (health.translation_plan_hits + translation_plan_misses: one per sequenced edit the engine flattened, an edit rebased to nothing included); must be 1.0: below it edits were rebased away, above it an edit took several rows"


def _edits(s):
    h = s["health"]
    return h["translation_plan_hits"] + h["translation_plan_misses"]


def read(ctx):
    inside = [s for t, s in ctx["parsed"] if ctx["w0"] <= t <= ctx["w1"]]
    if len(inside) < 2 or "translation_plan_hits" not in inside[0]["health"]:
        return None
    edits = _edits(inside[-1]) - _edits(inside[0])
    if not edits:
        return None
    return (inside[-1]["rows"] - inside[0]["rows"]) / edits
