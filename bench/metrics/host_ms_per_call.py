"""Host time per call of the entry, in ms: each ``run_scanned`` span minus
the device-busy time inside it, averaged over the traced calls."""


def reduce(run, cfg, device):
    t = run.get("trace")
    if not t:
        return None
    return t["host_ms_per_call"]
