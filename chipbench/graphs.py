"""The benchmark's graph data: the four Fig. 2 stand-ins, their directed
variants, seeded churn and Laplacians, made from a seed.

This is the benchmark's own copy of the generators in
``src/repro/graphs/generators.py`` (``real_graph_standin``,
``sensor_graph``, ``community_graph``, ``directed_variant``,
``edge_perturbation``) and of ``repro.core.fgft.laplacian``, so the data
the program and the reference both receive is made by neither.  The same
seed gives the same graphs as the originals.
"""
from __future__ import annotations

import numpy as np

#: name -> (n, |E|, family), as published for the paper's Fig. 2 graphs
STANDINS = {
    "minnesota": (2642, 3304, "sensor"),
    "human_protein": (3133, 6726, "scalefree"),
    "email": (1133, 5451, "scalefree"),
    "facebook": (2888, 2981, "community"),
}


def community_graph(n, n_comm=0, p_in=0.5, p_out=0.01, seed=0):
    rng = np.random.default_rng(seed)
    n_comm = n_comm or max(int(round(np.sqrt(n) / 2)), 2)
    labels = rng.integers(0, n_comm, n)
    same = labels[:, None] == labels[None, :]
    p = np.where(same, p_in, p_out)
    a = (rng.uniform(size=(n, n)) < p).astype(np.float32)
    a = np.triu(a, 1)
    return a + a.T


def sensor_graph(n, k=6, seed=0):
    rng = np.random.default_rng(seed)
    pts = rng.uniform(size=(n, 2))
    d2 = ((pts[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    np.fill_diagonal(d2, np.inf)
    a = np.zeros((n, n), np.float32)
    nn = np.argsort(d2, axis=1)[:, :k]
    a[np.repeat(np.arange(n), k), nn.ravel()] = 1.0
    return np.maximum(a, a.T)


def standin(name: str, seed: int = 0) -> np.ndarray:
    """(n, n) symmetric 0/1 adjacency with the graph's n and |E|."""
    n, m_target, family = STANDINS[name]
    rng = np.random.default_rng(seed)
    if family == "sensor":
        a = sensor_graph(n, k=3, seed=seed)
    elif family == "community":
        a = community_graph(n, n_comm=40, p_in=0.03, p_out=0.0002,
                            seed=seed)
    else:                               # preferential attachment
        a = np.zeros((n, n), np.float32)
        deg = np.ones(n)
        for v in range(1, n):
            k = 2 if v > 2 else 1
            p = deg[:v] / deg[:v].sum()
            for t in rng.choice(v, size=min(k, v), replace=False, p=p):
                a[v, t] = a[t, v] = 1.0
                deg[v] += 1
                deg[t] += 1
    edges = np.argwhere(np.triu(a, 1) > 0)
    m_now = len(edges)
    if m_now > m_target:
        for e in rng.choice(m_now, m_now - m_target, replace=False):
            i, j = edges[e]
            a[i, j] = a[j, i] = 0.0
    elif m_now < m_target:
        need = m_target - m_now
        while need > 0:
            i, j = rng.integers(0, n, 2)
            if i != j and a[i, j] == 0:
                a[i, j] = a[j, i] = 1.0
                need -= 1
    return a


def directed_variant(adj: np.ndarray, seed: int = 0) -> np.ndarray:
    """Each undirected edge keeps one direction, chosen with p = 0.5."""
    rng = np.random.default_rng(seed)
    upper = np.triu(adj, 1)
    coin = rng.uniform(size=adj.shape) < 0.5
    kept = np.where(coin, upper, 0)
    return (kept + (upper - kept).T).astype(np.float32)


def churn(adj: np.ndarray, num_edges: int, seed) -> np.ndarray:
    """The adjacency after one seeded churn batch of a symmetric graph:
    ``num_edges`` distinct pair slots, existing edges deleted (p = 0.5)
    or reweighted to U(0.25, 1), absent pairs given a unit edge (the
    semantics of ``edge_perturbation`` followed by its update)."""
    adj = np.array(adj, np.float32)
    n = adj.shape[0]
    rng = np.random.default_rng(seed)
    iu, ju = np.triu_indices(n, 1)
    occupied = adj[iu, ju] > 0
    take = min(int(num_edges), iu.size)
    for e in rng.choice(iu.size, size=take, replace=False):
        a, b = int(iu[e]), int(ju[e])
        if occupied[e]:
            w = 0.0 if rng.uniform() < 0.5 else float(rng.uniform(0.25, 1.0))
        else:
            w = 1.0
        adj[a, b] = adj[b, a] = w
    return adj


def laplacian(adj: np.ndarray) -> np.ndarray:
    """L = D - A (out-degree D for a directed graph), float32."""
    adj = np.asarray(adj, np.float64)
    return (np.diag(adj.sum(axis=1)) - adj).astype(np.float32)


def _neighbourhood(adj: np.ndarray, m: int) -> np.ndarray:
    """The subgraph on the first m nodes a breadth-first search from the
    highest-degree node reaches (a tiny graph that keeps edges)."""
    order = [int(np.argmax(adj.sum(axis=1)))]
    seen = set(order)
    for v in order:
        for u in np.flatnonzero(adj[v]):
            if len(order) < m and int(u) not in seen:
                seen.add(int(u))
                order.append(int(u))
    for u in range(adj.shape[0]):
        if len(order) >= m:
            break
        if u not in seen:
            seen.add(u)
            order.append(u)
    idx = np.asarray(order)
    return adj[np.ix_(idx, idx)]


def config_graphs(config: dict, rehearse: bool = False) -> list:
    """Adjacencies of a configuration's fleet, in its ``graphs`` order.
    A rehearsal keeps the leading ``rehearse_sizes`` nodes of each."""
    seed = int(config["graph_seed"])
    out = []
    for k, name in enumerate(config["graphs"]):
        a = standin(name, seed=seed)
        if rehearse:
            a = _neighbourhood(a, int(config["rehearse_sizes"][k]))
        if config["family"] == "general":
            a = directed_variant(a, seed=seed + k)
        out.append(a)
    return out
