"""The cell's inputs, made from ``--seed`` without the program's code.

An independent copy of the seeded generators the trainer uses, so that the
reference sees the same corpus, partition, channel and per-round draws as
the system under test while importing nothing of it:

* the synthetic corpus: class prototypes smoothed along time (sequences) or
  space (images), times a per-modality SNR, plus unit noise;
* a 20% held-out split, then K IID shards (``np.array_split`` of one
  permutation) with ``floor(omega * K)`` clients missing each modality, laid
  end to end around one permutation of the clients;
* the Table-2 wireless constants and the per-client costs of Eqs. 15-18;
* the client placement of the configuration's cell (``placement``), which
  the benchmark gives the program in place of the distances it draws;
* the experiment stream: K distance draws (unused), then per round K
  Rayleigh powers, one policy seed and K dropout seeds, in that order.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

#: Table 2 of the paper, plus the simulation constants it leaves open
WIRELESS = dict(B_max=10e6, tau_max=0.01, p_tx_dbm=23.0, N0_dbm_hz=-174.0,
                E_add=0.01, f_cpu=1.55e9, alpha=1e-27, cell_radius_m=500.0,
                extra_gain_db=60.0, beta0=100.0)
#: per-modality upload bits l_m and CPU cycles per sample beta_m (Table 2)
PROFILES = {
    "crema_d": {"audio": (562400.0, 2000.0), "image": (557056.0, 8000.0)},
    "iemocap": {"audio": (562400.0, 2000.0), "text": (1145280.0, 4500.0)},
}


def p_tx() -> float:
    return 10 ** (WIRELESS["p_tx_dbm"] / 10) / 1000.0


def n0() -> float:
    return 10 ** (WIRELESS["N0_dbm_hz"] / 10) / 1000.0


@dataclasses.dataclass
class Corpus:
    features: Dict[str, np.ndarray]
    labels: np.ndarray

    def subset(self, idx) -> "Corpus":
        return Corpus({m: x[idx] for m, x in self.features.items()},
                      self.labels[idx])


def _sequence(rng, labels, T, d, n_classes, snr):
    protos = rng.normal(size=(n_classes, T, d)).astype(np.float32)
    for _ in range(2):
        protos[:, 1:] = 0.5 * (protos[:, 1:] + protos[:, :-1])
    noise = rng.normal(size=(len(labels), T, d)).astype(np.float32)
    return (protos[labels] * snr + noise).astype(np.float32)


def _image(rng, labels, hw, n_classes, snr):
    protos = rng.normal(size=(n_classes, hw, hw, 3)).astype(np.float32)
    for _ in range(3):
        protos[:, 1:] = 0.5 * (protos[:, 1:] + protos[:, :-1])
        protos[:, :, 1:] = 0.5 * (protos[:, :, 1:] + protos[:, :, :-1])
    noise = rng.normal(size=(len(labels), hw, hw, 3)).astype(np.float32)
    return (protos[labels] * snr + noise).astype(np.float32)


def corpus(cfg: dict, seed: int, n: int) -> Corpus:
    """The synthetic corpus of ``cfg["dataset"]``; modalities are drawn in
    the order ``cfg["corpus"]`` lists them."""
    rng = np.random.default_rng(seed)
    C = cfg["n_classes"]
    labels = rng.integers(0, C, n).astype(np.int32)
    feats = {}
    for m, spec in cfg["corpus"]:
        if spec["kind"] == "sequence":
            feats[m] = _sequence(rng, labels, spec["T"], spec["d"], C,
                                 spec["snr"])
        else:
            feats[m] = _image(rng, labels, spec["hw"], C, spec["snr"])
    return Corpus(feats, labels)


@dataclasses.dataclass
class Client:
    data: Corpus
    modalities: Tuple[str, ...]

    @property
    def size(self) -> int:
        return len(self.data.labels)


@dataclasses.dataclass
class Inputs:
    """Everything a run of the cell consumes, as plain numpy."""
    mods: Tuple[str, ...]
    clients: List[Client]
    test: Corpus
    sizes: np.ndarray          # D_k
    has: np.ndarray            # [M, K] bool
    gamma: np.ndarray          # upload bits
    tau_cmp: np.ndarray
    e_cmp: np.ndarray
    dist_m: np.ndarray
    rng: np.random.Generator   # the experiment stream, after the distances


def _missing(K: int, omega: float, M: int, rng) -> np.ndarray:
    counts = np.full(M, int(np.floor(omega * K)))
    if counts.sum() > K * (M - 1):
        raise ValueError("omega removes more modalities than clients keep")
    order = rng.permutation(K)
    miss = np.zeros((M, K), bool)
    c = 0
    for m, n in enumerate(counts):
        miss[m, order[(c + np.arange(n)) % K]] = True
        c += int(n)
    return miss


def make_inputs(cfg: dict, seed: int) -> Inputs:
    K = cfg["K"]
    full = corpus(cfg, seed, cfg["n_samples"])
    rng = np.random.default_rng(seed)
    idx = rng.permutation(len(full.labels))
    n_test = int(cfg["test_frac"] * len(full.labels))
    train, test = full.subset(idx[n_test:]), full.subset(idx[:n_test])

    rng = np.random.default_rng(seed)
    shards = np.array_split(rng.permutation(len(train.labels)), K)
    mods = tuple(sorted(train.features))
    miss = _missing(K, cfg["omega"], len(mods), rng)
    clients = []
    for k in range(K):
        own = tuple(m for i, m in enumerate(mods) if not miss[i, k])
        sub = train.subset(shards[k])
        clients.append(Client(Corpus({m: sub.features[m] for m in own},
                                     sub.labels), own))

    W = WIRELESS
    prof = PROFILES[cfg["dataset"]]
    sizes = np.array([c.size for c in clients], np.float64)
    gamma = np.array([sum(prof[m][0] for m in c.modalities)
                      for c in clients])
    phi = np.array([sum(prof[m][1] + W["beta0"] for m in c.modalities)
                    - W["beta0"] for c in clients])
    tau_cmp = sizes * phi / W["f_cpu"]
    e_cmp = W["alpha"] * sizes * W["f_cpu"] ** 2 * phi
    has = ~miss

    stream = np.random.default_rng(seed)
    stream.uniform(0.02, 1.0, K)        # the program's own placement draw
    return Inputs(mods, clients, test, sizes, has, gamma, tau_cmp, e_cmp,
                  placement(cfg, seed), stream)


def placement(cfg: dict, seed: int) -> np.ndarray:
    """Client distances to the base station [m]: the K strata of the
    area-uniform radius on [sqrt(0.02), 1] x radius, in an order drawn from
    the seed.  Every seed places the same set of radii, so the share of
    clients that can meet tau_max, and with it the work a round does, does
    not move with the seed."""
    K = cfg["K"]
    u = 0.02 + 0.98 * (np.arange(K) + 0.5) / K
    order = np.random.default_rng([seed, 1]).permutation(K)
    return cfg["cell"]["radius_m"] * np.sqrt(u[order])


def draw_round(inp: Inputs) -> Tuple[np.ndarray, int, np.ndarray]:
    """One round of the experiment stream: (h [K], policy seed, dropout
    seeds [K])."""
    K = len(inp.dist_m)
    W = WIRELESS
    pl_db = 128.1 + 37.6 * np.log10(inp.dist_m / 1000.0)
    gain = 10 ** ((-pl_db + W["extra_gain_db"]) / 10.0)
    h = gain * inp.rng.exponential(1.0, K)
    seed = int(inp.rng.integers(2 ** 31))
    cseeds = np.array([inp.rng.integers(2 ** 31) for _ in range(K)],
                      np.uint32)
    return h, seed, cseeds
