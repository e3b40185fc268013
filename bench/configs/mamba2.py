"""Plain reference of the Mamba-2 language model (arXiv:2405.21060, SSD layer)
and its FLOP count, for the `mamba2` configurations of the benchmark.

Written from the paper in straightforward `jax.numpy`: the SSD layer is the
quadratic ("attention-like") form over the whole sequence, with no chunking,
no scan and no kernel. Parameters use the same names and nesting as the
program's checkpoints, so one set of weights drives both. Departures from
the published model, shared with the program under test and stated in the
configuration file: RMS norms scale by (1 + w) with w initialised to 0, and
the conv and projections carry no bias other than the conv's. The norm
epsilon is the file's `rms_norm_eps` in both.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp


def dims(model: dict) -> dict:
    d = model["hidden_size"]
    di = model["expand"] * d
    N, G, P = model["state_size"], model["n_groups"], model["head_dim"]
    H = di // P
    return dict(d=d, di=di, N=N, G=G, P=P, H=H, K=model["conv_kernel"],
                V=model["vocab_size"], L=model["num_hidden_layers"],
                conv=di + 2 * G * N, proj=2 * di + 2 * G * N + H)


def init_params(key, model: dict):
    """Seeded weights in float32, one jitted call: truncated normals scaled
    by 1/sqrt(fan-in) for the embedding and projections, N(0, 0.1²) conv
    taps, A = -[1..16] over the heads, D = 1, softplus(dt_bias) ≈ 0.01,
    norm gains at 0."""
    s = dims(model)
    L, H = s["L"], s["H"]

    def tn(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
                / math.sqrt(fan_in))

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 4)
        block = {"ln1": jnp.zeros((L, s["d"]), jnp.float32),
                 "mamba": {
                     "in_proj": tn(ks[1], (L, s["d"], s["proj"]), s["d"]),
                     "conv_w": 0.1 * jax.random.normal(
                         ks[2], (L, s["K"], s["conv"]), jnp.float32),
                     "conv_b": jnp.zeros((L, s["conv"]), jnp.float32),
                     "A_log": jnp.broadcast_to(
                         jnp.log(jnp.linspace(1.0, 16.0, H)), (L, H)),
                     "D": jnp.ones((L, H), jnp.float32),
                     "dt_bias": jnp.full((L, H), -4.6, jnp.float32),
                     "norm": jnp.zeros((L, s["di"]), jnp.float32),
                     "out_proj": tn(ks[3], (L, s["di"], s["d"]), s["di"])}}
        return {"embed": {"embedding": tn(ks[0], (s["V"], s["d"]), s["V"])},
                "final_norm": jnp.zeros((s["d"],), jnp.float32),
                "stages": [(block,)]}

    return make(key)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _block(p, x, s, eps):
    """One Mamba-2 block on (B, L, d), residual excluded."""
    B, L, _ = x.shape
    di, G, N, H, P = s["di"], s["G"], s["N"], s["H"], s["P"]
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + s["conv"]],
                  zxbcdt[..., di + s["conv"]:])
    K = p["conv_w"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + L] * p["conv_w"][k] for k in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :di].reshape(B, L, H, P)
    Bm = xbc[..., di:di + G * N].reshape(B, L, G, N)
    Cm = xbc[..., di + G * N:].reshape(B, L, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])                       # (B, L, H)
    a = dt * -jnp.exp(p["A_log"])                                 # log-decay
    cs = jnp.cumsum(a, axis=1)                                    # (B, L, H)
    # decay[b, h, t, s] = exp(sum_{s < i <= t} a_i) for s <= t, else 0
    diff = cs.transpose(0, 2, 1)[..., :, None] - cs.transpose(0, 2, 1)[..., None, :]
    causal = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    hg = H // G
    scores = jnp.einsum("btgn,bsgn->bgts", Cm, Bm)                # (B, G, L, L)
    scores = jnp.repeat(scores, hg, axis=1) * decay               # (B, H, L, L)
    y = jnp.einsum("bhts,bshp->bthp", scores, xs * dt[..., None])
    y = y + xs * p["D"][:, None]
    y = _rms(y.reshape(B, L, di) * jax.nn.silu(z), p["norm"], eps)
    return y @ p["out_proj"]


def loss(params, tokens, targets, model: dict, dtype=jnp.float32):
    """Mean next-token cross-entropy of `tokens` (B, L) against `targets`,
    computed in `dtype` (float32 for the reference, bfloat16 for its
    control); the log-softmax is taken in `dtype` too."""
    s = dims(model)
    eps = model["rms_norm_eps"]
    params = jax.tree.map(lambda x: x.astype(dtype), params)
    emb = params["embed"]["embedding"]
    h = emb[tokens] * jnp.asarray(math.sqrt(s["d"]), dtype)
    (block,) = params["stages"][0]
    for layer in range(s["L"]):
        p = jax.tree.map(lambda x: x[layer], block)
        h = h + _block(p["mamba"], _rms(h, p["ln1"], eps), s, eps)
    h = _rms(h, params["final_norm"], eps)
    logits = h @ emb.T
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked).astype(jnp.float32)


def forward_flops(model: dict, batch: int, seq: int) -> float:
    """Forward FLOPs of one client batch: 2 per weight per token for every
    matmul weight (the tied unembedding included, the embedding lookup
    not), plus the SSD layer's own products — C·Bᵀ, the score-weighted sum
    of x and the chunk states in and out — counted as the chunked
    algorithm with chunks of `chunk_size` does them."""
    s = dims(model)
    tokens = batch * seq
    per_layer = s["d"] * s["proj"] + s["di"] * s["d"] + s["K"] * s["conv"]
    matmul = 2 * tokens * (s["L"] * per_layer + s["V"] * s["d"])
    Q = min(model["chunk_size"], seq)
    nc = seq // Q
    ssd = (2 * batch * nc * s["G"] * Q * Q * s["N"]
           + 2 * batch * nc * s["H"] * Q * Q * s["P"]
           + 2 * 2 * batch * seq * s["H"] * s["P"] * s["N"])
    return float(matmul + s["L"] * ssd)


def train_flops(model: dict, batch: int, seq: int) -> float:
    """Forward and backward of one client gradient: 3 × the forward."""
    return 3.0 * forward_flops(model, batch, seq)


# --- the client task: the program's synthetic corpus, copied -----------------

def token_stream(n_tokens: int, vocab: int, seed: int, order: int = 2):
    """The synthetic corpus the program's LM task samples from: a Markov
    chain over a hashed context (a copy of the program's generator, so the
    reference makes its own tokens)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    n_states = 4096
    prefs = rng.integers(0, vocab, size=(n_states, 8))
    toks = np.zeros(n_tokens, np.int32)
    h = 0
    mix = rng.integers(1, 1 << 30, size=order) | 1
    for t in range(n_tokens):
        if rng.random() < 0.15:
            nxt = rng.integers(0, vocab)
        else:
            nxt = prefs[h % n_states, rng.integers(0, 8)]
        toks[t] = nxt
        h = (h * 1315423911 + int(nxt) * int(mix[t % order])) & 0x7FFFFFFF
    return toks


def reference_payload(config: dict, dtype=jnp.float32, fault=None):
    """payload(w, client, key) -> (loss, grad): client `client` draws `batch`
    windows of `seq + 1` tokens from its contiguous n-th of the corpus, at
    offsets from `key`, and returns the loss and its gradient at `w` —
    computed in `dtype`, with matmuls at "highest" for float32. With the
    fault "half_batch" only the first half of the windows count."""
    model, clients = config["model"], config["clients"]
    toks = jnp.asarray(token_stream(clients["corpus_tokens"], model["vocab_size"],
                                    clients["corpus_seed"]))
    n, batch, seq = clients["n_clients"], clients["batch"], clients["seq"]
    per = clients["corpus_tokens"] // n
    precision = "highest" if dtype == jnp.float32 else "default"

    @jax.jit
    def grad(w, client, key):
        with jax.default_matmul_precision(precision):
            starts = client * per + jax.random.randint(key, (batch,), 0, per - seq - 1)
            window = toks[starts[:, None] + jnp.arange(seq + 1, dtype=jnp.int32)[None]]
            if fault == "half_batch":
                window = window[:batch // 2]
            return jax.value_and_grad(loss)(w, window[:, :-1], window[:, 1:],
                                            model, dtype)

    return lambda w, client, key: grad(w, jnp.int32(client), key)


# --- the program under test ---------------------------------------------------

def build_program(config: dict, traffic, weights, mesh):
    """The chunked AFL trainer as `repro.launch.train` builds it for
    `--arch <arch> --layers <n>`, with the file's norm epsilon: the
    published model cut in depth, the LM task on the synthetic corpus, the
    rule's aggregator and the paper's sqrt(n/T) schedule — started from the
    benchmark's `weights`."""
    from repro.configs.registry import afl_config
    from repro.core.aggregators import make_aggregator
    from repro.core.fl_tasks import make_lm_task
    from repro.core.scan_staleness import make_chunked_staleness_runner
    from repro.launch.train import _parser, model_config
    from repro.optim import sqrt_nt_schedule

    prog, clients, model = config["program"], config["clients"], config["model"]
    args = _parser().parse_args(prog["argv"])
    # the registry's norm epsilon is not the published one; the file's holds
    cfg = dataclasses.replace(model_config(args), norm_eps=model["rms_norm_eps"])
    stated = {"d_model": model["hidden_size"], "num_layers": model["num_hidden_layers"],
              "ssm_state": model["state_size"], "ssm_head_dim": model["head_dim"],
              "ssm_expand": model["expand"], "ssm_conv": model["conv_kernel"],
              "ssm_chunk": model["chunk_size"], "ssm_groups": model["n_groups"],
              "vocab_size": model["vocab_size"], "norm_eps": model["rms_norm_eps"],
              "dtype": "float32"}
    got = {k: getattr(cfg, k) for k in stated}
    if got != stated:
        raise ValueError(f"program config {got} differs from the file {stated}")
    n = clients["n_clients"]
    aflc = afl_config(args.arch, algorithm=traffic.algorithm, n_clients=n,
                      delay_beta=traffic.beta, cache_dtype=clients["cache_dtype"],
                      k_batch=traffic.k_batch)
    task = make_lm_task(cfg=cfg, n_clients=n, batch=clients["batch"],
                        seq=clients["seq"], n_tokens=clients["corpus_tokens"],
                        seed=clients["corpus_seed"])
    shapes = jax.tree.map(lambda x: (x.shape, x.dtype), task.params0)
    mine = jax.tree.map(lambda x: (x.shape, x.dtype), weights)
    if shapes != mine:
        raise ValueError("the benchmark's weights do not match the model's parameters")
    grad_fn = task.grad_fn
    del task   # its own starting model is not used
    return make_chunked_staleness_runner(
        mesh=mesh, grad_fn=grad_fn, params0=weights,
        aggregator=make_aggregator(aflc), n_clients=n, T=traffic.T,
        beta=traffic.beta,
        server_lr=sqrt_nt_schedule(traffic.lr_scale, n, traffic.T),
        tau_max=traffic.tau_max, speed_skew=traffic.speed_skew, layout="tree",
        history_dtype=clients["history_dtype"], guards=False,
        resync_every=None, checkify_invariants=False, k_batch=traffic.k_batch)


def tick_work(config: dict, traffic) -> dict:
    """What one server tick must do at the least: K client gradients."""
    c = config["clients"]
    return {"flops": traffic.k_batch * train_flops(config["model"], c["batch"], c["seq"]),
            "bytes": 0.0}
