"""Device idle time under the host spans ``mfl.decode`` and ``mfl.export``
per traced call, in ms: decoding the records and writing the host mirrors
(``bench/scopes.py``)."""
from bench.scopes import idle_ms_per_call


def reduce(run, cfg, device):
    return idle_ms_per_call(run, cfg, ("mfl.decode", "mfl.export"))
