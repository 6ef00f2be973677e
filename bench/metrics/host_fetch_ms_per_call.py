"""Device idle time under the host span ``mfl.fetch`` per traced call, in
ms: after the program's last op, until its per-round outputs are on the host
(``bench/scopes.py``)."""
from bench.scopes import idle_ms_per_call


def reduce(run, cfg, device):
    return idle_ms_per_call(run, cfg, ("mfl.fetch",))
