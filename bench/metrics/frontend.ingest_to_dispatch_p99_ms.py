"""p99 of the frontend's own per-event latency, arrival until the round's
``guarded_step`` returned (``ServingFrontend.event_latencies``): ingest
and batching wait plus host dispatch, without the device's work."""


def read(rec):
    q = rec.get("ingest_to_dispatch_p99_s")
    return None if q is None else q * 1e3
