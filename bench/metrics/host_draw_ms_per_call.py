"""Device idle time under the host spans ``mfl.draw_xs`` and
``mfl.dispatch`` per traced call, in ms: everything before the round program
starts (``bench/scopes.py``)."""
from bench.scopes import idle_ms_per_call


def reduce(run, cfg, device):
    return idle_ms_per_call(run, cfg, ("mfl.draw_xs", "mfl.dispatch"))
