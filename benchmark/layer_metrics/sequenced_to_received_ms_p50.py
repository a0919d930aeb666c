NAME = "sequenced_to_received_ms_p50"
UNIT = "ms"
LAYER = "fan-out + firehose (fanout/plane.py, fanout/writer.py, fleet_consumer.pump)"
MOVES = "apply_lag_p50_ms"
READS = "status lines, window delta of op_clock.sequenced_to_received (the sequencer's wire stamp of a feed's oldest line -> the feed handed to the engine's ingest_lines, its recv loop just ended): the median over the window's rows; holds whatever the parent process does between stamping an op and flushing its frame, the writer thread's wake-up, the socket and the consumer's wake-up together; absent where the status lines carry no op_clock"


def read(ctx):
    from layer_metrics import sequenced_to_applied_ms_p50 as oc

    return oc.percentile_ms(oc.stage_delta(ctx, "sequenced_to_received"), 0.5)
