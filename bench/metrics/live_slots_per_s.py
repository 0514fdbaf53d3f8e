"""Slots admitted for the whole fleet per second, over the whole window."""


def read(run):
    lat = run.window.get("latency_s")
    if lat is None or len(lat) == 0:
        return None
    return len(lat) / run.window["window_s"]
