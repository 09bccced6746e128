"""Plain reference for the ``paper_mlp.*`` configurations.

The paper's decentralized Bayes-by-Backprop round (Sec. 2.1, eqs. 5-6),
written out in straightforward ``jax.numpy`` and NumPy from its
description, importing nothing of the system under test:

* data: the ``mnist_like`` generator (class prototypes plus Gaussian noise,
  the {4, 9} pair confusable), split iid over the agents;
* model: a ReLU MLP (784-200-200-10) with a mean-field Gaussian posterior
  per agent, sigma = softplus(rho);
* local phase: ``u`` Adam steps on the free energy
  ``kl_scale * KL(q || prior) + E_q[sum of cross-entropy over the batch]``,
  the prior being the agent's posterior at the start of the round;
* consensus, eq. (6): ``prec_i = sum_j W_ij prec_j``,
  ``mu_i = sum_j W_ij prec_j mu_j / prec_i``, over the round's W;
* gossip: each directed edge of the graph fires in a window as a Poisson
  process; an agent with a fired in-edge merges with the fired edges'
  weights and keeps the idle in-edges' weight on itself ("conserve").

Where the semantics are a seeded random stream (the data, the batches, the
initial weights, the Monte-Carlo noise, the gossip firings) the reference
draws the same stream from the run's seed with the same NumPy and
``jax.random`` calls, so that one seed gives one trajectory: a
Bayes-by-Backprop loss is a random number, and two different draws could
only be compared statistically.  The reference takes no array from the
program.

``dtype`` selects the precision: float32 with every matmul at
``Precision.HIGHEST`` (the reference), or bfloat16 throughout (the
control, one step below the configuration's float32).  What the reference
does not implement (another dataset, partition, graph generator, clock,
optimizer or wire precision) is refused, not approximated.  The agent axis is processed in blocks, so that the reference fits on the chip
once the program's state is freed.
"""
from __future__ import annotations

import functools
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def mnist_like(seed: int, dim: int, n_classes: int, n_train_per_class: int,
               n_test_per_class: int, noise: float = 0.55,
               proto_scale: float = 1.0, confusable_pairs=((4, 9),),
               confusable_gap: float = 0.35):
    """(x_train, y_train, x_test, y_test): prototypes N(0, scale^2) per
    class, the second of each confusable pair one coordinate away from the
    first, rows = prototype + N(0, noise^2), shuffled."""
    rng = np.random.default_rng(seed)
    protos = rng.normal(0.0, proto_scale, (n_classes, dim))
    for a, b in confusable_pairs:
        direction = np.zeros(dim)
        direction[rng.integers(dim)] = 1.0
        protos[b] = protos[a] + confusable_gap * proto_scale * direction

    def sample(n_per_class):
        xs, ys = [], []
        for c in range(n_classes):
            xs.append(protos[c] + rng.normal(0.0, noise, (n_per_class, dim)))
            ys.append(np.full(n_per_class, c))
        x = np.concatenate(xs).astype(np.float32)
        y = np.concatenate(ys).astype(np.int32)
        perm = rng.permutation(len(y))
        return x[perm], y[perm]

    x_train, y_train = sample(n_train_per_class)
    x_test, y_test = sample(n_test_per_class)
    return x_train, y_train, x_test, y_test


def iid_shards(n_rows: int, n_agents: int, seed: int) -> list:
    """Row indices of each agent's shard: one shuffle, split evenly."""
    perm = np.random.default_rng(seed).permutation(n_rows)
    return np.array_split(perm, n_agents)


# ---------------------------------------------------------------------------
# graphs and gossip windows
# ---------------------------------------------------------------------------


def torus_rows(rows: int, cols: int) -> list:
    out = []
    for r in range(rows):
        for c in range(cols):
            i = r * cols + c
            nbrs = [i, ((r - 1) % rows) * cols + c, ((r + 1) % rows) * cols + c,
                    r * cols + (c - 1) % cols, r * cols + (c + 1) % cols]
            out.append(sorted(dict.fromkeys(nbrs)))
    return out


def _connected(rows: list) -> bool:
    seen = {0}
    todo = deque([0])
    while todo:
        i = todo.popleft()
        for j in rows[i]:
            if j not in seen:
                seen.add(j)
                todo.append(j)
    return len(seen) == len(rows)


def watts_strogatz_rows(n: int, k: int, beta: float, seed: int,
                        attempts: int = 100) -> list:
    """Ring lattice with k/2 neighbours a side, each lattice edge rewired
    with probability beta to a uniform new target; resampled from the
    (seed, attempt) stream until connected."""
    for attempt in range(attempts):
        rng = np.random.default_rng([seed, attempt])
        nbrs = [set() for _ in range(n)]
        for off in range(1, k // 2 + 1):
            for i in range(n):
                j = (i + off) % n
                nbrs[i].add(j)
                nbrs[j].add(i)
        for off in range(1, k // 2 + 1):
            for i in range(n):
                j = (i + off) % n
                if rng.random() < beta and j in nbrs[i] and len(nbrs[i]) < n - 1:
                    while True:
                        t = int(rng.integers(n))
                        if t != i and t not in nbrs[i]:
                            break
                    nbrs[i].discard(j)
                    nbrs[j].discard(i)
                    nbrs[i].add(t)
                    nbrs[t].add(i)
        rows = [sorted(s | {i}) for i, s in enumerate(nbrs)]
        if _connected(rows):
            return rows
    raise RuntimeError("no connected Watts-Strogatz sample")


def graph_rows(topology: dict) -> list:
    """In-neighbour lists (self included, ascending) of the config's graph;
    every row weighs its neighbours equally."""
    kind, params = topology["graph"], topology["params"]
    if kind == "torus":
        return torus_rows(params["rows"], params["cols"])
    if kind == "watts_strogatz" and topology["edge_native"]:
        # the edge-native generator; the dense one draws another graph
        return watts_strogatz_rows(params["n"], params["k"], params["beta"],
                                   params["seed"])
    raise ValueError(f"the reference has no graph {kind!r} "
                     f"(edge_native={topology['edge_native']})")


def dense_w(rows: list) -> np.ndarray:
    n = len(rows)
    w = np.zeros((n, n), np.float64)
    for i, r in enumerate(rows):
        w[i, r] = 1.0 / len(r)
    return w


class PoissonWindows:
    """Window r of Poisson gossip at ``rate * window_len`` firings per
    directed edge: K ~ Poisson(E mu) picks of uniform edges, the unique ones
    fire (the superposition of E independent Poisson processes), drawn from
    ``default_rng([seed, r])``."""

    def __init__(self, rows: list, rate: float, window_len: float, seed: int):
        n = len(rows)
        self.n = n
        dst, src, w = [], [], []
        self.diag = np.zeros(n)
        for i, r in enumerate(rows):
            for j in r:
                if j == i:
                    self.diag[i] = 1.0 / len(r)
                else:
                    dst.append(i)
                    src.append(j)
                    w.append(1.0 / len(r))
        self.dst = np.asarray(dst, np.int64)
        self.src = np.asarray(src, np.int64)
        self.w = np.asarray(w, np.float64)
        self.mu = rate * window_len
        self.seed = int(seed)
        self.offdiag_sum = np.bincount(self.dst, weights=self.w, minlength=n)
        self.deg = np.bincount(self.dst, minlength=n)

    def window(self, r: int, up=None) -> tuple[np.ndarray, np.ndarray]:
        """(W-tilde [N, N] float32, active [N] bool) of window r; with
        ``up``, a fired edge whose either end is down does not count."""
        rng = np.random.default_rng([self.seed, r])
        e = self.dst.shape[0]
        k = int(rng.poisson(e * self.mu))
        fired = (np.unique(rng.integers(0, e, size=k)) if k
                 else np.zeros(0, np.int64))
        if up is not None:
            fired = fired[up[self.dst[fired]] & up[self.src[fired]]]
        f_dst = self.dst[fired]
        count = np.bincount(f_dst, minlength=self.n)
        fsum = np.bincount(f_dst, weights=self.w[fired], minlength=self.n)
        active = count > 0
        w_self = np.where(count == self.deg, self.diag,
                          self.diag + (self.offdiag_sum - fsum))
        w_self = np.where(active, w_self, 1.0)
        wt = np.zeros((self.n, self.n), np.float32)
        wt[np.arange(self.n), np.arange(self.n)] = w_self.astype(np.float32)
        wt[f_dst, self.src[fired]] = self.w[fired].astype(np.float32)
        return wt, active


# ---------------------------------------------------------------------------
# agent faults and the quarantine guard
# ---------------------------------------------------------------------------

CRASH_SALT, CORRUPT_SALT = 0xC7A54, 0xBADBAD
HUGE_FILL = 1.0e30  # finite garbage: caught by the magnitude bound alone
QUARANTINE_BOUND = 1e20  # |prec| or |prec * mu| above this is garbage
_FILL_KINDS = ("nan", "inf", "huge")


class Faults:
    """Agent churn and payload corruption, one draw per window from salted
    streams of the fault seed: an up agent crashes with ``crash_rate``, a
    down one recovers with ``recover_rate`` (all up in window 0); an up
    agent corrupts its transmitted statistics with ``corrupt_rate``,
    filled with NaN, inf or a huge finite value (``mix``: drawn per
    agent)."""

    def __init__(self, n: int, seed: int, crash_rate: float = 0.0,
                 recover_rate: float = 0.5, corrupt_rate: float = 0.0,
                 corrupt_kind: str = "mix"):
        self.n, self.seed = n, int(seed)
        self.crash_rate, self.recover_rate = crash_rate, recover_rate
        self.corrupt_rate, self.kind = corrupt_rate, corrupt_kind
        self._up = [np.ones(n, bool)]

    def up(self, r: int) -> np.ndarray:
        while len(self._up) <= r:
            t = len(self._up)
            u = np.random.default_rng([self.seed, CRASH_SALT, t]).random(self.n)
            self._up.append(np.where(self._up[-1], u >= self.crash_rate,
                                     u < self.recover_rate))
        return self._up[r]

    def corrupted(self, r: int) -> np.ndarray:
        if self.corrupt_rate <= 0.0:
            return np.zeros(self.n, bool)
        rng = np.random.default_rng([self.seed, CORRUPT_SALT, r])
        return (rng.random(self.n) < self.corrupt_rate) & self.up(r)

    def fills(self, r: int) -> tuple[np.ndarray, np.ndarray]:
        if self.kind == "mix":
            rng = np.random.default_rng([self.seed, CORRUPT_SALT, r])
            rng.random(self.n)
            pick = rng.integers(0, 3, self.n)
        else:
            pick = np.full(self.n, _FILL_KINDS.index(self.kind))
        mean = np.choose(pick, [np.nan, np.inf, HUGE_FILL]).astype(np.float32)
        rho = np.choose(pick, [np.nan, 0.0, 0.0]).astype(np.float32)
        return mean, rho


def payload_valid(mean, rho):
    """[N]: every lane of an agent's (prec, prec * mu) finite, prec > 0,
    both within the quarantine bound."""
    prec = 1.0 / jnp.square(jax.nn.softplus(rho))
    pm = prec * mean
    ok = (jnp.isfinite(prec) & (prec > 0) & (prec <= QUARANTINE_BOUND)
          & jnp.isfinite(pm) & (jnp.abs(pm) <= QUARANTINE_BOUND))
    return jnp.all(ok, axis=-1)


def quarantine_w(w: np.ndarray, valid_src: np.ndarray) -> np.ndarray:
    """Drop every invalid source's column (the diagonal stays) and move the
    dropped row mass onto self."""
    n = w.shape[0]
    keep = valid_src[None, :] | np.eye(n, dtype=bool)
    wk = np.where(keep, w, np.float32(0)).astype(np.float32)
    dropped = np.sum(w - wk, axis=1, dtype=np.float32)
    wk[np.arange(n), np.arange(n)] += dropped
    return wk


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


def layer_sizes(model: dict) -> list:
    return [model["input_dim"]] + [model["hidden"]] * model["depth"] + [
        model["n_classes"]]


def leaves(sizes) -> list:
    """(name, shape) of each parameter in flat order: the parameter dict's
    keys sorted (b1, b2, ..., w1, w2, ...)."""
    n = len(sizes) - 1
    shapes = {f"w{i}": (sizes[i - 1], sizes[i]) for i in range(1, n + 1)}
    shapes.update({f"b{i}": (sizes[i],) for i in range(1, n + 1)})
    return [(k, shapes[k]) for k in sorted(shapes)]


def leaf_slices(sizes) -> dict:
    out, off = {}, 0
    for name, shape in leaves(sizes):
        size = int(np.prod(shape))
        out[name] = (off, off + size)
        off += size
    return out


def unflatten(theta, sizes) -> dict:
    out = {}
    for name, (a, b) in leaf_slices(sizes).items():
        shape = dict(leaves(sizes))[name]
        out[name] = theta[..., a:b].reshape(theta.shape[:-1] + shape)
    return out


def init_mean(key, sizes) -> jax.Array:
    """Shared initial weights: N(0, 1/fan_in) per weight, zero biases."""
    ks = jax.random.split(key, len(sizes) - 1)
    params = {}
    for i, (k, fan_in, fan_out) in enumerate(
            zip(ks, sizes[:-1], sizes[1:]), 1):
        params[f"w{i}"] = jax.random.normal(k, (fan_in, fan_out)) / np.sqrt(fan_in)
        params[f"b{i}"] = jnp.zeros((fan_out,))
    return jnp.concatenate([params[k].reshape(-1) for k, _ in leaves(sizes)])


def logits(theta, x, sizes):
    p = unflatten(theta, sizes)
    n = len(sizes) - 1
    h = x
    for i in range(1, n):
        h = jax.nn.relu(h @ p[f"w{i}"] + p[f"b{i}"])
    return h @ p[f"w{n}"] + p[f"b{n}"]


def nll(theta, x, y, sizes):
    """Summed softmax cross-entropy over the batch."""
    lg = logits(theta, x, sizes)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


def softplus_inv(y):
    return jnp.log(jnp.expm1(y))


def kl(mq, rq, mp, rp):
    sq, sp = jax.nn.softplus(rq), jax.nn.softplus(rp)
    return jnp.sum(jnp.log(sp / sq)
                   + (jnp.square(sq) + jnp.square(mq - mp)) / (2.0 * jnp.square(sp))
                   - 0.5)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def _local_block(sizes, inf, u, fault, dtype):
    """u Adam steps for a block of agents (vmapped), one round."""
    n_mc, kl_scale = inf["n_mc_samples"], inf["kl_scale"]
    b1, b2, eps_adam = 0.9, 0.999, 1e-8

    def free_energy(m, r, pm, pr, x, y, key):
        keys = jax.random.split(key, n_mc)

        def one(k):
            eps = jax.random.normal(k, m.shape, jnp.float32).astype(dtype)
            return nll(m + jax.nn.softplus(r) * eps, x, y, sizes)

        if fault == "half_batch":
            # half of the batch left out, the sum scaled to the whole batch
            half = x.shape[0] // 2
            x = jnp.concatenate([x[:half], x[:half]])
            y = jnp.concatenate([y[:half], y[:half]])
        enll = jnp.mean(jax.vmap(one)(keys))
        return kl_scale * kl(m, r, pm, pr) + enll

    def agent(m, r, mu_m, mu_r, nu_m, nu_r, step, x, y, key, lr):
        pm, pr = m, r
        keys = jax.random.split(key, u)

        def body(carry, xs):
            m, r, mu_m, mu_r, nu_m, nu_r, step = carry
            xb, yb, k = xs
            loss, (gm, gr) = jax.value_and_grad(free_energy, argnums=(0, 1))(
                m, r, pm, pr, xb, yb, k)
            t = (step + 1).astype(jnp.float32)
            bc1 = (1.0 - b1 ** t).astype(dtype)
            bc2 = (1.0 - b2 ** t).astype(dtype)
            out = []
            for p, g, mu, nu in ((m, gm, mu_m, nu_m), (r, gr, mu_r, nu_r)):
                mu = b1 * mu + (1 - b1) * g
                nu = b2 * nu + (1 - b2) * jnp.square(g)
                p = p - lr * (mu / bc1) / (jnp.sqrt(nu / bc2) + eps_adam)
                out.append((p, mu, nu))
            (m, mu_m, nu_m), (r, mu_r, nu_r) = out
            return (m, r, mu_m, mu_r, nu_m, nu_r, step + 1), loss

        carry, losses = jax.lax.scan(
            body, (m, r, mu_m, mu_r, nu_m, nu_r, step), (x, y, keys))
        return carry + (jnp.mean(losses),)

    return jax.jit(jax.vmap(agent, in_axes=(0,) * 10 + (None,)))


def _eq6_rows(w_rows, prec, pm):
    new_prec = w_rows @ prec
    return (w_rows @ pm) / new_prec, softplus_inv(jax.lax.rsqrt(new_prec))


# what the reference implements, beyond the defaults it shares
SUPPORTED = {("model", "name"): "mlp", ("data", "dataset"): "mnist_like",
             ("data", "partition"): "iid", ("inference", "optimizer"): "adam",
             ("inference", "wire_dtype"): "f32",
             ("inference", "shared_init"): True}
INFERENCE_KEYS = ("optimizer", "lr", "lr_decay", "kl_scale", "init_sigma",
                  "shared_init", "n_mc_samples", "wire_dtype",
                  # how the program computes eq. (6), not what it computes
                  "consensus_impl")


def check_supported(cfg: dict, traffic: dict) -> None:
    for (section, key), want in SUPPORTED.items():
        if cfg[section][key] != want:
            raise ValueError(f"the reference implements {section}.{key} = "
                             f"{want!r}, not {cfg[section][key]!r}")
    extra = sorted(set(cfg["inference"]) - set(INFERENCE_KEYS))
    if extra:
        raise ValueError(f"the reference does not implement inference {extra}")
    if cfg["data"]["partition_params"]:
        raise ValueError("the reference's iid split takes no parameters")
    clock = traffic.get("clock")
    if clock is not None and clock["kind"] != "poisson":
        raise ValueError(f"the reference has no {clock['kind']!r} clock")


class Trainer:
    """The reference network, state kept in agent blocks."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, *,
                 dtype=jnp.float32, fault: str | None = None,
                 block: int = 256):
        check_supported(cfg, traffic)
        self.sizes = layer_sizes(cfg["model"])
        self.inf = cfg["inference"]
        data = cfg["data"]
        self.u, self.bsz = data["local_updates"], data["batch_size"]
        self.n = cfg["n_agents"]
        self.dtype, self.fault = dtype, fault
        self.block = min(block, self.n)
        if self.n % self.block:
            raise ValueError("the agent block must divide N")
        x, y, self.x_test, self.y_test = mnist_like(seed, **data["dataset_params"])
        shards = iid_shards(len(y), self.n, seed)
        self.shard_n = np.asarray([len(s) for s in shards], np.int32)
        width = int(self.shard_n.max())
        idx = np.stack([np.resize(s, width) for s in shards])
        self.x = jnp.asarray(x[idx]).astype(dtype)  # [N, rows, dim]
        self.y = jnp.asarray(y[idx])
        self.rows = graph_rows(cfg["topology"])
        clock = traffic.get("clock")
        self.windows = (None if clock is None else PoissonWindows(
            self.rows, clock["rate"], clock.get("window_len", 1.0), seed))
        fdoc = traffic.get("faults")
        self.faults = (None if not fdoc else Faults(self.n, seed, **fdoc))
        self.quarantine = traffic.get("fault_policy") == "quarantine"
        self.n_quarantined = 0
        self.w_static = dense_w(self.rows).astype(np.float32)
        key = jax.random.key(seed)
        key, k_init = jax.random.split(key)
        self.key = key
        self.mean0 = init_mean(k_init, self.sizes)
        self.rho0 = float(np.log(np.expm1(self.inf["init_sigma"])))
        p = self.mean0.shape[0]
        nb = self.n // self.block
        full = lambda v: jnp.full((self.block, p), v, dtype)
        self.state = [dict(
            m=jnp.broadcast_to(self.mean0.astype(dtype), (self.block, p)),
            r=full(self.rho0), mu_m=full(0), mu_r=full(0), nu_m=full(0),
            nu_r=full(0), step=jnp.zeros((self.block,), jnp.int32))
            for _ in range(nb)]
        self.round_idx = 0
        self._local = _local_block(self.sizes, self.inf, self.u, fault, dtype)

    @functools.cached_property
    def _sample(self):
        u, b = self.u, self.bsz

        def per_agent(k, x_a, y_a, n_a):
            idx = jax.random.randint(k, (u * b,), 0, n_a)
            return (x_a[idx].reshape((u, b) + x_a.shape[1:]),
                    y_a[idx].reshape(u, b))

        return jax.jit(jax.vmap(per_agent))

    def round(self) -> float:
        """One round; returns the mean over agents of their mean step loss."""
        self.key, k_batch, k_round = jax.random.split(self.key, 3)
        bkeys = jax.random.split(k_batch, self.n)
        akeys = jax.random.split(k_round, self.n)
        xs, ys = self._sample(bkeys, self.x, self.y, jnp.asarray(self.shard_n))
        lr = jnp.asarray(self.inf["lr"], jnp.float32) * jnp.float32(
            self.inf["lr_decay"]) ** jnp.float32(self.round_idx)
        lr = lr.astype(self.dtype)
        up = (np.ones(self.n, bool) if self.faults is None
              else self.faults.up(self.round_idx))
        losses = []
        for bi, st in enumerate(self.state):
            sl = slice(bi * self.block, (bi + 1) * self.block)
            *new, loss = self._local(
                st["m"], st["r"], st["mu_m"], st["mu_r"], st["nu_m"],
                st["nu_r"], st["step"], xs[sl], ys[sl], akeys[sl], lr)
            train = jnp.asarray(up[sl])  # a crashed agent keeps its state
            for k, v in zip(("m", "r", "mu_m", "mu_r", "nu_m", "nu_r",
                             "step"), new):
                mask = train.reshape((-1,) + (1,) * (v.ndim - 1))
                st[k] = jnp.where(mask, v, st[k])
            losses.append(jnp.where(train, loss.astype(jnp.float32), jnp.nan))
        del xs, ys
        if self.fault != "no_exchange":
            self._consensus(up)
        self.round_idx += 1
        return float(jnp.nanmean(jnp.concatenate(losses)))

    def _consensus(self, up: np.ndarray) -> None:
        if self.windows is None:
            w, active = self.w_static, np.ones(self.n, bool)
        else:
            w, active = self.windows.window(
                self.round_idx, None if self.faults is None else up)
        active = active & up
        m = jnp.concatenate([st["m"] for st in self.state])
        r = jnp.concatenate([st["r"] for st in self.state])
        if self.quarantine:
            w, m, r, act_ok = self._quarantine(w, m, r)
            active = active & act_ok
        w = jnp.asarray(w).astype(self.dtype)
        prec = 1.0 / jnp.square(jax.nn.softplus(r))
        pm = prec * m
        del m, r
        eq6 = jax.jit(_eq6_rows)
        for bi, st in enumerate(self.state):
            sl = slice(bi * self.block, (bi + 1) * self.block)
            new_m, new_r = eq6(w[sl], prec, pm)
            act = jnp.asarray(active[sl])[:, None]
            st["m"] = jnp.where(act, new_m, st["m"])
            st["r"] = jnp.where(act, new_r, st["r"])

    def _quarantine(self, w, m, r):
        """The exchange-boundary guard: what each agent transmits (its
        posterior, or the fill when it corrupts) is validated; invalid
        sources leave every other row (their weight moves to self) and an
        agent whose own state is invalid does not merge.  Returns the
        guarded W, the transmitted statistics with invalid rows replaced by
        finite placeholders, and who may merge."""
        t = self.round_idx
        bad = jnp.asarray(self.faults.corrupted(t))[:, None]
        fill_m, fill_r = (jnp.asarray(f)[:, None] for f in self.faults.fills(t))
        m_src = jnp.where(bad, fill_m.astype(self.dtype), m)
        r_src = jnp.where(bad, fill_r.astype(self.dtype), r)
        valid_src = np.asarray(payload_valid(m_src, r_src))
        valid_self = np.asarray(payload_valid(m, r))
        self.n_quarantined += int((~valid_src).sum())
        v_src, v_self = valid_src[:, None], valid_self[:, None]
        m_x = jnp.where(v_src, m_src, jnp.where(v_self, m, 0.0))
        r_x = jnp.where(v_src, r_src, jnp.where(v_self, r, 1.0))
        return quarantine_w(np.asarray(w, np.float32), valid_src), m_x, r_x, \
            valid_self

    def leaf_norms(self, which: str) -> dict:
        """Norm over all agents of each leaf of ``which``: ``grad`` (Adam's
        first moment, mean and rho), or ``change`` (posterior minus the
        initial posterior)."""
        sl = leaf_slices(self.sizes)
        sq = {}
        for st in self.state:
            if which == "grad":
                parts = {"mean": st["mu_m"], "rho": st["mu_r"]}
            else:
                parts = {"mean": st["m"] - self.mean0.astype(self.dtype),
                         "rho": st["r"] - jnp.asarray(self.rho0, self.dtype)}
            for kind, arr in parts.items():
                a32 = arr.astype(jnp.float32)
                for name, (a, b) in sl.items():
                    key = f"{kind}.{name}"
                    sq[key] = sq.get(key, 0.0) + float(
                        jnp.sum(jnp.square(a32[:, a:b])))
        return {k: float(np.sqrt(v)) for k, v in sq.items()}


def train_readings(cfg: dict, traffic: dict, seed: int, rounds: int, *,
                   dtype=jnp.float32, fault: str | None = None) -> dict:
    """Per-round losses, per-leaf norms of Adam's first moment after round
    1, and per-leaf norms of the posterior's change after ``rounds``."""
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        tr = Trainer(cfg, traffic, seed, dtype=dtype, fault=fault)
        losses = [tr.round()]
        grad = tr.leaf_norms("grad")
        losses += [tr.round() for _ in range(rounds - 1)]
        change = tr.leaf_norms("change")
    out = {"loss": losses, "grad": grad, "change": change}
    if tr.quarantine:
        out["quarantined"] = tr.n_quarantined
    return out
