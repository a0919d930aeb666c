NAME = "compact_lanes_per_ack"
UNIT = "count"
LAYER = "engine (doc_batch_engine.py)"
MOVES = "apply_lag_p50_ms"
READS = "status lines, window delta: health.compacted_lanes (lanes the compactions dispatched, pow2 padding included; a fleet-wide compaction adds the fleet's capacity) over health.acks_seen (documents whose feed carried a summaryAck): 1.0-2.0 where a compaction costs the acked documents, the fleet's size where it costs the fleet; absent where the program counts neither"


def read(ctx):
    inside = [s["health"] for t, s in ctx["parsed"]
              if ctx["w0"] <= t <= ctx["w1"]]
    if len(inside) < 2 or "compacted_lanes" not in inside[0]:
        return None
    first, last = inside[0], inside[-1]
    acks = last["acks_seen"] - first["acks_seen"]
    if not acks:
        return None
    return (last["compacted_lanes"] - first["compacted_lanes"]) / acks
