"""Device self time under the round program's ``round/gather``
scope per traced round, in ms (``bench/scopes.py``)."""
from bench.scopes import stage_ms_per_round


def reduce(run, cfg, device):
    return stage_ms_per_round(run, cfg, "gather")
