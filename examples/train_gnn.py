"""Graph node classification with paddle.geometric message passing.

Reference analog: the paddle.geometric message-passing workflow
(python/paddle/geometric/message_passing/send_recv.py) that PGL-style GNNs
build on: host-side neighbor sampling + reindexing feeds a jitted
device step whose GraphConv layers are gather + segment-reduce
compositions (static ``out_size`` keeps every shape static under jit).

Run:

    env JAX_PLATFORMS=cpu \
        python examples/train_gnn.py --steps 40

The synthetic task is community detection: nodes belong to k communities,
intra-community edges dominate, and features are noisy one-hot hints —
so a model that aggregates neighbors beats a featurewise classifier and
the loss collapse demonstrates real message passing.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_community_graph(rs, n_nodes, n_comm, n_edges, feat_dim, p_intra=0.9):
    """Edges mostly intra-community; features = noisy community hints."""
    import numpy as np
    comm = rs.randint(0, n_comm, n_nodes)
    src, dst = [], []
    while len(src) < n_edges:
        a = rs.randint(0, n_nodes)
        if rs.rand() < p_intra:
            peers = np.flatnonzero(comm == comm[a])
        else:
            peers = np.flatnonzero(comm != comm[a])
        b = int(peers[rs.randint(0, len(peers))])
        src.append(a)
        dst.append(b)
    x = 0.3 * rs.randn(n_nodes, feat_dim)
    x[np.arange(n_nodes), comm] += 1.0  # weak hint in the first k dims
    return (x.astype("float32"), np.asarray(src), np.asarray(dst), comm)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=256)
    ap.add_argument("--edges", type=int, default=2048)
    ap.add_argument("--communities", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--steps", type=int, default=40)
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp

    import paddle_tpu
    import paddle_tpu.geometric as G
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    from paddle_tpu.nn.functional_call import functional_call, state

    rs = np.random.RandomState(0)
    feat_dim = max(16, args.communities)
    x_np, src, dst, comm = make_community_graph(
        rs, args.nodes, args.communities, args.edges, feat_dim)

    class GraphConv(nn.Layer):
        """h_v = W_self x_v + W_neigh mean_{u->v} x_u  (GCN-mean flavor:
        the reference's send_u_recv('mean') aggregation under a Linear)."""

        def __init__(self, in_dim, out_dim, n_nodes):
            super().__init__()
            self.self_lin = nn.Linear(in_dim, out_dim)
            self.neigh_lin = nn.Linear(in_dim, out_dim)
            self.n_nodes = n_nodes

        def forward(self, x, src, dst):
            agg = G.send_u_recv(x, src, dst, reduce_op="mean",
                                out_size=self.n_nodes)
            return self.self_lin(x) + self.neigh_lin(agg)

    class GNN(nn.Layer):
        def __init__(self, in_dim, hidden, n_classes, n_nodes):
            super().__init__()
            self.c1 = GraphConv(in_dim, hidden, n_nodes)
            self.c2 = GraphConv(hidden, hidden, n_nodes)
            self.head = nn.Linear(hidden, n_classes)

        def forward(self, x, src, dst):
            h = nn.functional.relu(self.c1(x, src, dst))
            h = nn.functional.relu(self.c2(h, src, dst))
            return self.head(h)

    model = GNN(feat_dim, args.hidden, args.communities, args.nodes)
    params, buffers = state(model)
    o = opt.AdamW(learning_rate=5e-3)
    ostate = o.init(params)

    x = jnp.asarray(x_np)
    src_j = jnp.asarray(src, jnp.int32)
    dst_j = jnp.asarray(dst, jnp.int32)
    y = jnp.asarray(comm)

    @jax.jit
    def step(p, os_, x):
        def loss_fn(p):
            logits, _ = functional_call(model, p, buffers, (x, src_j, dst_j))
            return nn.functional.cross_entropy(logits, y)
        loss, g = jax.value_and_grad(loss_fn)(p)
        newp, nos = o.update(g, os_, p)
        return newp, nos, loss

    first = last = None
    for i in range(args.steps):
        params, ostate, loss = step(params, ostate, x)
        lv = float(loss)
        first = lv if first is None else first
        last = lv
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  loss {lv:.4f}", flush=True)

    logits, _ = functional_call(model, params, buffers, (x, src_j, dst_j))
    acc = float(jnp.mean(jnp.argmax(logits, -1) == y))
    print(f"train accuracy {acc:.3f}  (loss {first:.3f} -> {last:.3f})")
    assert last < 0.5 * first, "GNN did not learn"
    assert acc > 0.9, "community detection should be easy for a GNN"

    # the sampling workflow: minibatch a seed set, reindex, run the same
    # conv layers on the subgraph (host preprocessing -> static shapes)
    order = np.argsort(dst, kind="stable")
    row = src[order]
    colptr = np.zeros(args.nodes + 1, np.int64)
    np.add.at(colptr[1:], dst, 1)
    colptr = np.cumsum(colptr)
    seeds = np.arange(32)
    neigh, cnt = G.sample_neighbors(row, colptr, seeds, sample_size=8)
    r_src, r_dst, nodes = G.reindex_graph(seeds, neigh, cnt)
    sub_logits, _ = functional_call(
        GNN(feat_dim, args.hidden, args.communities, len(nodes)),
        params, buffers,
        (x[jnp.asarray(nodes)], jnp.asarray(r_src, jnp.int32),
         jnp.asarray(r_dst, jnp.int32)))
    print(f"sampled-subgraph forward: {len(nodes)} nodes -> "
          f"logits {tuple(sub_logits.shape)}")


if __name__ == "__main__":
    main()
