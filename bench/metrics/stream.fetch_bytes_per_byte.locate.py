"""Bytes copied from the device to the host per byte of window sent to the
device: the Recorder counter ``fetch_bytes`` over the counter
``put_bytes`` (core/stream.py).  A dense (P, N) bool mask per N-byte
window reads P, however many new bytes the stream's last window holds;
positions compacted on the device would read far less.  None where the
program counts no ``fetch_bytes`` or no ``put_bytes``."""


def read(ctx):
    rec = ctx.get("recorder") or {}
    counters = rec.get("metrics", {}).get("counters", {})
    fetched = counters.get("fetch_bytes")
    put = counters.get("put_bytes")
    if not fetched or not put:
        return None
    return fetched / put
