"""The general traffic generator: one traffic mix (a `bench/traffic/<name>.json`
file of parameters) and a seed give the arrival stream a run feeds the server.

An AFL server's traffic is its arrival process: at every server tick `k_batch`
distinct clients arrive, drawn by participation weight (log-spaced over a
`(1 + speed_skew)²` range, so the fastest client arrives `(1+s)²` times as often
as the slowest), each with a staleness drawn from Exp(beta). The stream is the
noise behind those draws — one Gumbel row over the clients per tick (top-k of
log-weight + Gumbel samples K distinct clients by weight) and one Exp(beta) draw
per arrival — made on the device in one call, `stream_chunks` chunks long. The
timed loop cycles through it, so a faster server never runs off its end, and
every seed gets the same sizes and arrival counts.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

#: "never leaves" / "never rejoins" in the availability-window arrays
NEVER = np.iinfo(np.int32).max

TRAFFIC_KEYS = ("algorithm", "k_batch", "chunk_events", "beta", "speed_skew",
                "lr_scale", "T", "stream_chunks", "check_chunks")


@dataclasses.dataclass(frozen=True)
class Traffic:
    """One mix: the server rule, arrivals per tick (K), ticks per chunk, the
    staleness scale, the participation skew, the learning-rate constant of
    the paper's sqrt(n/T) schedule, the server-iteration budget T (far past
    any window), the pre-made stream's length in chunks, and how many
    leading chunks the reference follows to decide `correct`."""
    algorithm: str
    k_batch: int
    chunk_events: int
    beta: float
    speed_skew: float
    lr_scale: float
    T: int
    stream_chunks: int
    check_chunks: int

    @property
    def tau_max(self) -> int:
        # the protocol's history bound for Exp(beta) staleness (6·beta + 20)
        return int(6 * self.beta + 20)


def load(path: Path) -> Traffic:
    raw = json.loads(Path(path).read_text())
    missing = [k for k in TRAFFIC_KEYS if k not in raw]
    if missing:
        raise ValueError(f"{path}: traffic mix lacks {missing}")
    t = Traffic(**{k: raw[k] for k in TRAFFIC_KEYS})
    if (t.k_batch < 1 or t.chunk_events < 1 or t.check_chunks < 1
            or t.stream_chunks < t.check_chunks):
        raise ValueError(f"{path}: bad sizes in {t}")
    return t


def root_key(seed: int):
    """A PRNG key for any whole seed: the low 32 bits make the key and the
    rest is folded in, so seeds past 2**32 stay distinct."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF)),
                              np.uint32((seed >> 32) & 0xFFFFFFFF))


def participation_log_probs(n: int, speed_skew: float) -> np.ndarray:
    """log of the clients' arrival probabilities: weights log-spaced in
    [1/(1+s), 1+s], normalised (uniform when s = 0)."""
    if speed_skew > 0:
        w = np.exp(np.linspace(-np.log(1 + speed_skew), np.log(1 + speed_skew), n))
    else:
        w = np.ones(n)
    return np.log(w / w.sum()).astype(np.float32)


@dataclasses.dataclass
class Stream:
    """Per-chunk device arrays the timed loop feeds the server, made before
    the window so that no slicing compiles inside it."""
    gumbels: tuple          # stream_chunks × (C, n) f32
    tau_raw: tuple          # stream_chunks × (C,) f32, or (C, K) for K > 1
    leave_at: jax.Array     # (n,) int32 — every client always available
    rejoin_at: jax.Array    # (n,) int32

    def chunk(self, i: int):
        """Inputs of the i-th chunk, cycling through the stream."""
        j = i % len(self.gumbels)
        return self.gumbels[j], self.tau_raw[j]


def make_stream(key, traffic: Traffic, n_clients: int) -> Stream:
    """The mix's arrival noise for `n_clients` clients, made on the device in
    one jitted call from `key`."""
    S, C, K = traffic.stream_chunks, traffic.chunk_events, traffic.k_batch
    tau_shape = (S, C) if K == 1 else (S, C, K)

    @jax.jit
    def draw(key):
        kg, kt = jax.random.split(key)
        g = jax.random.gumbel(kg, (S, C, n_clients), jnp.float32)
        tau = jax.random.exponential(kt, tau_shape, jnp.float32) * traffic.beta
        return tuple(g[i] for i in range(S)), tuple(tau[i] for i in range(S))

    gumbels, tau_raw = draw(key)
    never = jnp.full((n_clients,), NEVER, jnp.int32)
    return Stream(gumbels, tau_raw, never, never)
