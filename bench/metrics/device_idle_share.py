"""Share of the traced window in which no op ran on the device, in %."""


def reduce(run, cfg, device):
    t = run.get("trace")
    if not t:
        return None
    return 100.0 * t["idle_share"]
