"""DistGCN 1.5D hybrid-parallel sparse matmul (reference
``gpu_ops/DistGCN_15d.py:19-60``).

The reference's algorithm on ``size`` GPUs with replication factor ``r``:
the adjacency matrix is row-partitioned over ``size/r`` row shards and its
contraction (column) range is split over ``r`` replicas; each step of the
stage loop **broadcasts** one feature block within a column group
(``col_groups[rank_col].dlarrayBroadcast``), accumulates a local ``csrmm``
over that block, and finally **all-reduces** the partial products across the
row group (``row_groups[rank_c].dlarrayNcclAllReduce``).

TPU-native redesign: the same movement expressed over a 2-axis device mesh
``(gr=size/r, gc=r)`` inside one ``shard_map``:

- features ``H`` are row-sharded over BOTH axes (gc-major, matching the
  reference's global row partition over all ``size`` processes);
- ``all_gather(H, 'gr')`` materializes exactly the column slice the stage
  loop's broadcasts deliver (same bytes, one fused ICI collective instead of
  ``stages`` point broadcasts);
- each device multiplies its local COO block (rows = its gr shard, columns =
  its gc slice) against the gathered slice;
- ``psum(partial, 'gc')`` is the row-group allreduce.

XLA lowers the gather/psum to ICI collectives and overlaps them with the
segment-sum compute — the scheduling the reference hand-writes with streams.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def partition_adjacency(rows: np.ndarray, cols: np.ndarray,
                        values: np.ndarray, n_nodes: int,
                        gr: int, gc: int):
    """Partition a COO adjacency for the (gr, gc) mesh.

    Returns ``(vals, local_rows, local_cols)`` each shaped
    ``(gr, gc, nnz_max)`` — device (i, j) owns entries with
    ``row in [i*Nr, (i+1)*Nr)`` and ``col in [j*Nc, (j+1)*Nc)``, with local
    indices. Zero-padded to the max block nnz (padded entries have value 0
    and indices 0, contributing nothing to the segment sum).
    """
    assert n_nodes % gr == 0 and n_nodes % gc == 0, \
        "pad the graph so n_nodes divides both mesh axes"
    nr, nc = n_nodes // gr, n_nodes // gc
    # single sort pass instead of gr*gc boolean scans of the nnz arrays
    bi = rows // nr
    bj = cols // nc
    order = np.lexsort((bj, bi))
    rows, cols, values = rows[order], cols[order], values[order]
    block_key = bi[order] * gc + bj[order]
    splits = np.searchsorted(block_key, np.arange(gr * gc + 1))
    counts = np.diff(splits)
    nnz_max = int(counts.max()) if counts.size else 0
    vals = np.zeros((gr, gc, nnz_max), np.float32)
    lrows = np.zeros((gr, gc, nnz_max), np.int32)
    lcols = np.zeros((gr, gc, nnz_max), np.int32)
    for k in range(gr * gc):
        i, j = divmod(k, gc)
        lo, hi = splits[k], splits[k + 1]
        vals[i, j, :hi - lo] = values[lo:hi]
        lrows[i, j, :hi - lo] = rows[lo:hi] - i * nr
        lcols[i, j, :hi - lo] = cols[lo:hi] - j * nc
    return vals, lrows, lcols


def spmm_15d(mesh: Mesh, adj_parts, h, n_nodes: int,
             gr_axis: str = "gr", gc_axis: str = "gc"):
    """``Z = A @ H`` with the 1.5D schedule on ``mesh``.

    ``adj_parts``: output of :func:`partition_adjacency`, device-put with
    leading dims sharded ``P(gr_axis, gc_axis)``. ``h``: (N, F) sharded
    ``P((gc_axis, gr_axis), None)``. Returns Z with the same sharding as h's
    row partition over gr (replicated over gc).
    """
    gr = mesh.shape[gr_axis]
    nr = n_nodes // gr

    def local(vals, lrows, lcols, h_local):
        from ..kernels import csr_spmm
        vals, lrows, lcols = vals[0, 0], lrows[0, 0], lcols[0, 0]
        # the column-group broadcast stages: one tiled all_gather over gr
        h_slice = jax.lax.all_gather(h_local, gr_axis, axis=0, tiled=True)
        # hetukern csr_spmm (docs/KERNELS.md): the local block product goes
        # through the kernel registry — inside this shard_map the named-axis
        # eligibility guard keeps auto mode on the gather+segment_sum
        # fallback (identical to the pre-hetukern expression)
        z = csr_spmm.coo_matmat(vals, lrows, lcols, nr, h_slice)
        # the row-group allreduce over the contraction split
        return jax.lax.psum(z, gc_axis)

    spec_adj = P(gr_axis, gc_axis, None)
    spec_h = P((gc_axis, gr_axis), None)
    spec_z = P(gr_axis, None)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(spec_adj, spec_adj, spec_adj, spec_h),
                         out_specs=spec_z)(*adj_parts, h)


def shard_gcn_inputs(mesh: Mesh, rows, cols, values, h, n_nodes,
                     gr_axis="gr", gc_axis="gc"):
    """Host-side helper: partition + device_put the adjacency and features
    with the shardings :func:`spmm_15d` expects."""
    gr, gc = mesh.shape[gr_axis], mesh.shape[gc_axis]
    parts = partition_adjacency(np.asarray(rows), np.asarray(cols),
                                np.asarray(values), n_nodes, gr, gc)
    spec_adj = NamedSharding(mesh, P(gr_axis, gc_axis, None))
    adj = tuple(jax.device_put(p, spec_adj) for p in parts)
    h = jax.device_put(np.asarray(h, np.float32),
                       NamedSharding(mesh, P((gc_axis, gr_axis), None)))
    return adj, h


def gcn_forward(mesh, adj_parts, h, weights, n_nodes,
                gr_axis="gr", gc_axis="gc"):
    """Multi-layer GCN forward: Z_l = relu(A @ H_l @ W_l); final layer has no
    relu (logits). Weights are replicated; XLA keeps Z row-sharded over gr."""
    for i, w in enumerate(weights):
        z = spmm_15d(mesh, adj_parts, h, n_nodes, gr_axis, gc_axis)
        h = z @ w
        if i < len(weights) - 1:
            # re-shard activations to the (gc, gr) row partition for the
            # next layer's gather (the logits keep their natural P(gr) shard)
            h = jax.lax.with_sharding_constraint(
                jax.nn.relu(h),
                NamedSharding(mesh, P((gc_axis, gr_axis), None)))
    return h
