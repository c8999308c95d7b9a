"""Plain reference of what ``DecentralizedTrainer.run`` computes, in blocks of
whole runs, for the cells' algorithms.  It imports nothing of the program.

Event process (host, numpy), replayed draw for draw from the seeds:

- the Erdos-Renyi graph of ``topology.erdos_renyi``: an upper triangle of
  ``random((n, n)) < p`` from ``default_rng(graph_seed)``, and if it is not
  connected a random Hamiltonian cycle from the same generator;
- the paper_default time model: ``default_rng(straggler_seed)``, each
  computation ``base · lognormal(0, jitter)``, times ``slowdown`` with
  probability ``straggler_prob``; a set of workers draws one lognormal
  vector and then one uniform vector, a single worker one scalar of each;
- dsgd_aau: workers finish in (time, worker) order; an event fires when the
  newest finisher has a finished neighbour in another component of the
  epoch's spanning forest.  Every finished worker then takes a gradient
  step, Metropolis-averages over the graph edges among the finished set,
  and restarts.  When the forest spans all workers the epoch restarts;
- ad_psgd: the finisher waits for the averaging lock (``avg_time``), picks
  a uniform neighbour, the pair averages with weight 1/2, and only the
  finisher steps and restarts;
- dsgd_sync: every event is a barrier of all workers, mixed with the
  Metropolis matrix of the whole graph.

Every ``run()`` starts a new event process at virtual time 0; the time
model's and the scheduler's generators carry over.  What a run draws
follows how the program pulls events: dsgd_aau draws a finished clique's
next completion times only when the next event is asked for, ad_psgd draws
the finisher's next time with its event, and the dense ``scan`` loop pulls
one event past the run's bound.

Update (device, JAX, HIGHEST precision), per event on its m workers
``a`` with consensus submatrix P (P is the identity on every other worker):

    G_a = grad loss(S_a, pool[a, ptr_a mod pool])
    W_a' = P^T (W_a - eta * gm_a * G_a),   y_a' = P^T y_a
    S_a' = W_a' where rm_a else S_a,       ptr_a' = ptr_a + rm_a

The m rows are gathered, updated and written back; to compile one program
per power of two rather than one per event size, the rows are padded to
the next power of two with lanes that gather row 0, carry no weight and
are dropped by the write-back.  The history loss is the loss of
mean_i(W_i / y_i) every ``eval_every`` events and once more at the end of
each run.
"""
from __future__ import annotations

import functools
import heapq
from typing import Iterator, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Event = Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]


# -- event process ---------------------------------------------------------

def _connected(adj: np.ndarray) -> bool:
    seen = np.zeros(len(adj), dtype=bool)
    seen[0] = True
    frontier = [0]
    while frontier:
        nxt = np.flatnonzero(adj[frontier].any(axis=0) & ~seen)
        seen[nxt] = True
        frontier = nxt.tolist()
    return bool(seen.all())


def erdos_renyi(n: int, p: float, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    adj = np.triu(rng.random((n, n)) < p, k=1)
    adj = adj | adj.T
    if not _connected(adj):
        perm = rng.permutation(n)
        adj[perm, np.roll(perm, 1)] = True
        adj[np.roll(perm, 1), perm] = True
        np.fill_diagonal(adj, False)
    return adj


class PaperTimes:
    """Computation times of the paper's straggler protocol."""

    rng_methods = ("many", "one")     # the only draws after construction

    def __init__(self, n, seed, straggler_prob=0.1, slowdown=10.0,
                 jitter=0.05, base_time=1.0, heterogeneity=0.0):
        self.rng = np.random.default_rng(seed)
        self.p, self.slow, self.jitter = straggler_prob, slowdown, jitter
        if heterogeneity > 0:
            self.base = base_time * self.rng.lognormal(0.0, heterogeneity, n)
        else:
            self.base = np.full(n, float(base_time))

    def many(self, workers) -> np.ndarray:
        t = self.base[np.asarray(workers)].astype(np.float64)
        if self.jitter > 0:
            t = t * self.rng.lognormal(0.0, self.jitter, t.shape)
        return np.where(self.rng.random(t.shape) < self.p, t * self.slow, t)

    def one(self, worker: int) -> float:
        t = self.base[worker]
        if self.jitter > 0:
            t = t * self.rng.lognormal(0.0, self.jitter)
        if self.rng.random() < self.p:
            t = t * self.slow
        return float(t)


def metropolis(adj: np.ndarray) -> np.ndarray:
    """P[a, b] = 1 / (1 + max(deg a, deg b)) on edges, rows summing to 1."""
    deg = adj.sum(axis=1)
    P = np.where(adj, 1.0 / (1.0 + np.maximum(deg[:, None], deg[None, :])),
                 0.0)
    P[np.diag_indices_from(P)] = 1.0 - P.sum(axis=1)
    return P


def aau_process(adj: np.ndarray, times: PaperTimes) -> Iterator[Event]:
    n = len(adj)
    nbrs = [np.flatnonzero(row) for row in adj]
    heap: List[Tuple[float, int]] = []
    for i, dt in enumerate(times.many(np.arange(n))):
        heapq.heappush(heap, (float(dt), i))
    finished = np.zeros(n, dtype=bool)
    comp = np.arange(n)
    while True:
        t, i = heapq.heappop(heap)
        finished[i] = True
        if n > 1:
            nb = nbrs[i]
            link = nb[finished[nb] & (comp[nb] != comp[i])]
            if not link.size:
                continue
            for j in link:
                if comp[j] != comp[i]:
                    comp[comp == comp[j]] = comp[i]
        fin = np.flatnonzero(finished)
        lanes = np.ones(len(fin), dtype=bool)
        yield fin, metropolis(adj[np.ix_(fin, fin)]), lanes, lanes
        for j, dt in zip(fin.tolist(), times.many(fin)):
            heapq.heappush(heap, (t + float(dt), j))
        finished[:] = False
        if n > 1 and np.all(comp == comp[0]):
            comp = np.arange(n)


def adpsgd_process(adj: np.ndarray, times: PaperTimes,
                   pick_rng: np.random.Generator,
                   lock_dt: float) -> Iterator[Event]:
    n = len(adj)
    nbrs = [np.flatnonzero(row) for row in adj]
    heap: List[Tuple[float, int]] = []
    for i, dt in enumerate(times.many(np.arange(n))):
        heapq.heappush(heap, (float(dt), i))
    half = np.full((2, 2), 0.5)
    lock_free_at = 0.0
    while True:
        t, i = heapq.heappop(heap)
        nb = nbrs[i]
        if len(nb):
            t = max(t, lock_free_at) + lock_dt
            lock_free_at = t
            r = int(nb[pick_rng.integers(0, len(nb))])
            pair = np.array(sorted((i, r)))
            lanes = pair == i
            ev = (pair, half, lanes, lanes)
        else:
            one = np.ones(1, dtype=bool)
            ev = (np.array([i]), np.ones((1, 1)), one, one)
        heapq.heappush(heap, (t + times.one(i), i))
        yield ev


def sync_process(adj: np.ndarray, times: PaperTimes) -> Iterator[Event]:
    n = len(adj)
    P = metropolis(adj)
    everyone = np.arange(n)
    lanes = np.ones(n, dtype=bool)
    while True:
        times.many(everyone)
        yield everyone, P, lanes, lanes


def make_process(config, traffic, seeds):
    """A factory of fresh event processes (one per run) sharing generators."""
    n = config["n_workers"]
    if config["topology"] != "erdos_renyi":
        raise ValueError(f"reference has no topology {config['topology']!r}")
    if traffic["scenario"] != "paper_default":
        raise ValueError(f"reference has no scenario {traffic['scenario']!r}")
    adj = erdos_renyi(n, config["edge_prob"], config["graph_seed"])
    times = PaperTimes(n, seeds["straggler"], **traffic.get("scenario_kw", {}))
    alg = traffic["algorithm"]
    if alg == "dsgd_aau":
        return lambda: aau_process(adj, times)
    if alg == "dsgd_sync":
        return lambda: sync_process(adj, times)
    if alg == "ad_psgd":
        pick = np.random.default_rng(seeds["sched"])
        lock = (traffic.get("scheduler_kw", {}).get("avg_time", 0.05)
                * traffic.get("scenario_kw", {}).get("base_time", 1.0))
        return lambda: adpsgd_process(adj, times, pick, lock)
    raise ValueError(f"reference has no algorithm {alg!r}")


# -- update --------------------------------------------------------------------

def _bcast(v, leaf):
    return v.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)


@functools.lru_cache(maxsize=None)
def _compiled(loss_fn, eval_fn):
    hi = jax.lax.Precision.HIGHEST
    grad = jax.grad(loss_fn)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def step(W, S, y, ptr, pool_x, pool_y, idx, P, gm, rm, eta):
        n = y.shape[0]
        live = idx < n
        rows = jnp.where(live, idx, 0)
        sel = ptr[rows] % pool_x.shape[1]
        G = jax.vmap(grad)(jax.tree.map(lambda s: s[rows], S),
                           {"x": pool_x[rows, sel], "y": pool_y[rows, sel]})

        def mix(w, g):
            wa = w[rows]
            stepped = (wa - _bcast(eta * gm, wa) * g).reshape(len(idx), -1)
            return jnp.einsum("ad,ab->bd", stepped, P,
                              precision=hi).reshape(wa.shape)

        Wa = jax.tree.map(mix, W, G)
        Sa = jax.tree.map(lambda s, w: jnp.where(_bcast(rm, w) > 0, w, s[rows]),
                          S, Wa)
        ya = jnp.einsum("a,ab->b", y[rows], P, precision=hi)

        def put(full, part):
            return full.at[idx].set(part, mode="drop")

        return (jax.tree.map(put, W, Wa), jax.tree.map(put, S, Sa),
                put(y, ya), put(ptr, ptr[rows] + rm.astype(ptr.dtype)))

    @jax.jit
    def history_loss(W, y, batch):
        avg = jax.tree.map(lambda x: jnp.mean(x / _bcast(y, x), axis=0), W)
        return eval_fn(avg, batch)[0]

    return step, history_loss


def padded_event(n: int, workers, P_sub, gl, rl, mix: bool = True):
    """An event's arrays padded to the next power of two of its size: pad
    lanes index row n (dropped on write-back) and carry no weight."""
    m = len(workers)
    width = 1 << max(0, (m - 1).bit_length())
    idx = np.full(width, n, np.int32)
    idx[:m] = workers
    P = np.zeros((width, width), np.float32)
    P[:m, :m] = P_sub if mix else np.eye(m)
    gm = np.zeros(width, np.float32)
    rm = np.zeros(width, np.int32)
    gm[:m] = gl
    rm[:m] = rl
    return idx, P, gm, rm


def replay(config, traffic, seeds, model, *, sizes, extra_pull: int,
           pools, eval_batch, observe, mix: bool = True):
    """One run of each size in ``sizes`` (events); ``observe(r, W, S, y,
    ptr, losses)`` is called after each run with the run's history losses.

    ``mix=False`` leaves the exchange between workers out (P = I): a fault
    planted in the reference, for reading what that fault does to the
    compared numbers.
    """
    n = config["n_workers"]
    every = traffic["eval_every"]
    pool_x, pool_y = pools
    process = make_process(config, traffic, seeds)
    with jax.default_matmul_precision("highest"):
        step, history_loss = _compiled(model.loss, model.evaluate)
        p0 = model.make_init(config)(jax.random.PRNGKey(seeds["init"]))
        W = jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape) + 0, p0)
        S = jax.tree.map(lambda x: x + 0, W)
        y = jnp.ones((n,), jnp.float32)
        ptr = jnp.zeros((n,), jnp.int32)
        eta = jnp.float32(config["eta0"])
        for r, E in enumerate(sizes):
            events = process()
            losses = []
            for k in range(E + extra_pull):
                ev = next(events)   # pulled even past E, as the program does
                if k >= E:
                    continue
                idx, P, gm, rm = padded_event(n, *ev, mix=mix)
                W, S, y, ptr = step(W, S, y, ptr, pool_x, pool_y, idx, P, gm,
                                    rm, eta)
                if (k + 1) % every == 0:
                    losses.append(history_loss(W, y, eval_batch))
            losses.append(history_loss(W, y, eval_batch))
            observe(r, W, S, y, ptr, [float(v) for v in jax.device_get(losses)])
