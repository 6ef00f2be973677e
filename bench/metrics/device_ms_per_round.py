"""Device-busy time of the traced window per round, in ms."""


def reduce(run, cfg, device):
    t = run.get("trace")
    if not t or not t["rounds"]:
        return None
    return 1e3 * t["busy_s"] / t["rounds"]
