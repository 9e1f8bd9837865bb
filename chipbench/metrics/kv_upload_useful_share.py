"""Share of the KV pool bytes handed to the device by the window's fused
steps that belong to live rows: the ``kv.upload`` spans' ``live_bytes``
over their ``bytes``, the steps' increments of ``BatchEngine.stats``
``kv_bytes_live`` and ``kv_bytes_uploaded``."""

from chipbench.phases import in_window


def read(run):
    ups = in_window(run, "kv.upload")
    sent = sum(r.attrs["bytes"] for r in ups)
    if not sent:
        return None
    return 100.0 * sum(r.attrs["live_bytes"] for r in ups) / sent
