"""1 - device-busy / traced window, averaged over the chips, in a run that
prices the multi-tenant edge's joint optimum."""


def read(run):
    v = run.view
    if v is None or not v.spans_named("bench.offline_opt_services"):
        return None
    return v.idle_share()
