NAME = "row_slots_scanned_share"
UNIT = "%"
LAYER = "kernels (ops/mergetree_kernel.py)"
MOVES = "apply_lag_p50_ms"
READS = "status lines, window delta: health.row_slots_scanned (the deepest take of every slice dispatched: where its row loop ended) over health.row_slots_dense (ops_per_step a slice: what a dense loop ran); 100 where every step fills its slots, absent where the program counts neither"


def read(ctx):
    inside = [s["health"] for t, s in ctx["parsed"]
              if ctx["w0"] <= t <= ctx["w1"]]
    if len(inside) < 2 or "row_slots_dense" not in inside[0]:
        return None
    first, last = inside[0], inside[-1]
    dense = last["row_slots_dense"] - first["row_slots_dense"]
    if not dense:
        return None
    return 100.0 * (
        last["row_slots_scanned"] - first["row_slots_scanned"]) / dense
