"""1 - device-busy / traced window, averaged over the chips."""


def read(run):
    v = run.view
    if v is None or not v.spans_named("bench.run_fleet"):
        return None
    return v.idle_share()
