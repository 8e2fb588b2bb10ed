"""Time-bin sequence parallelism — the long-context axis of the framework.

The reference scales huge spatio-temporal windows by decomposing intervals
into per-time-bin key ranges (Z3IndexKeySpace.getIndexValues:133-158) and
scanning them with a bounded client fan-out. Here that becomes a second mesh
axis: a 2D mesh ``(shard, bin)`` where the *data* is sharded over ``shard``
(horizontal partitioning) and the *bin-window space* — the query's temporal
extent, the analog of sequence length — is blocked over ``bin``. Each device
computes partial aggregates for its (data-shard x bin-block) tile; merges are
explicit XLA collectives (``psum``) over both axes, riding ICI.

For windows wider than device memory appetite, ``stream_chunks > 1`` streams
bin-blocks through a ``lax.scan`` (double-buffered by XLA), accumulating
partials — "ring over time bins, not tokens" (SURVEY.md §5).

Contract: the aggregate must be additive (count, density grids, histograms,
any sketch merged by ``+``) — both the cross-device psum and the scan
accumulation rely on it. Non-additive reductions (min/max) use the 1-D GSPMD
path in the executor.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import numpy as np


def mesh_2d(n_shard: int, n_bin: int):
    """A (shard, bin) 2-D device mesh: data parallel x bin-space parallel."""
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    need = n_shard * n_bin
    if len(devs) < need:
        raise ValueError(f"mesh_2d({n_shard}, {n_bin}) needs {need} devices, have {len(devs)}")
    return Mesh(
        np.array(devs[:need]).reshape(n_shard, n_bin),
        axis_names=("shard", "bin"),
    )


def pad_windows(
    starts: np.ndarray, ends: np.ndarray, multiple: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Pad the window axis to a multiple (padded windows are empty (0, 0))."""
    K = starts.shape[1]
    Kp = ((K + multiple - 1) // multiple) * multiple
    if Kp == K:
        return starts, ends
    pad = ((0, 0), (0, Kp - K))
    return (
        np.pad(starts, pad),
        np.pad(ends, pad),
    )


def build_bin_parallel(
    mesh,
    col_names,
    L: int,
    predicate: Callable,
    agg_fn: Callable,
    stream_chunks: int = 1,
):
    """Build the jitted (shard, bin) shard_map kernel.

    Returned callable takes ``(dev_cols, starts, ends, counts)`` already
    placed with :func:`placements` shardings. Separate from
    :func:`bin_parallel_run` so callers (the executor) can cache the
    compiled kernel across queries.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from geomesa_tpu.kernels import masks as kmasks

    col_spec = P("shard", None)
    win_spec = P("shard", "bin")

    def body(cols, starts, ends, counts):
        if stream_chunks == 1:
            m = kmasks.window_mask(starts, ends, counts, L)
            m = m & predicate(cols, jnp)
            part = agg_fn(cols, m, jnp)
        else:
            # sequence streaming: scan over bin-window chunks; each step's
            # windows are a slice of the local bin block
            k_loc = starts.shape[1]
            chunk = k_loc // stream_chunks

            def step(acc, i):
                s = jax.lax.dynamic_slice_in_dim(starts, i * chunk, chunk, 1)
                e = jax.lax.dynamic_slice_in_dim(ends, i * chunk, chunk, 1)
                m = kmasks.window_mask(s, e, counts, L)
                m = m & predicate(cols, jnp)
                p = agg_fn(cols, m, jnp)
                return jax.tree.map(jnp.add, acc, p), None

            shapes = jax.eval_shape(
                lambda c: agg_fn(c, jnp.zeros((c[next(iter(c))].shape[0], L), bool), jnp),
                cols,
            )
            # shard_map carries are typed varying over the manual axes:
            # the pcast marks the zeros accordingly
            init = jax.tree.map(
                lambda sd: jax.lax.pcast(jnp.zeros(sd.shape, sd.dtype),
                                         ("shard", "bin"), to="varying"),
                shapes,
            )
            part, _ = jax.lax.scan(step, init, jnp.arange(stream_chunks))
        # explicit merge over both mesh axes (ICI collectives)
        return jax.tree.map(lambda p: jax.lax.psum(p, ("shard", "bin")), part)

    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                {k: col_spec for k in col_names},
                win_spec,
                win_spec,
                P("shard"),
            ),
            out_specs=P(),  # prefix spec: every leaf fully replicated post-psum
        )
    )


def placements(mesh):
    """(column, window, count) NamedShardings for :func:`build_bin_parallel`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return (
        NamedSharding(mesh, P("shard", None)),
        NamedSharding(mesh, P("shard", "bin")),
        NamedSharding(mesh, P("shard")),
    )


def bin_parallel_run(
    mesh,
    cols: Dict[str, "np.ndarray"],
    starts: np.ndarray,
    ends: np.ndarray,
    counts: np.ndarray,
    L: int,
    predicate: Callable,
    agg_fn: Callable,
    stream_chunks: int = 1,
):
    """Place inputs and run mask+aggregate over a (shard, bin) mesh.

    ``cols``: [S, L] column arrays (S divisible by the shard axis size).
    ``starts``/``ends``: [S, K] per-bin scan windows (padded here to the bin
    axis x ``stream_chunks``). ``predicate(cols, jnp)``: fused fine filter;
    ``agg_fn(cols, mask, jnp)``: additive partial aggregate (pytree).

    Returns the merged aggregate (fully replicated). Convenience wrapper —
    hot paths use :func:`build_bin_parallel` + :func:`placements` and cache.
    """
    import jax

    n_bin = mesh.shape["bin"]
    starts, ends = pad_windows(starts, ends, n_bin * stream_chunks)
    fn = build_bin_parallel(
        mesh, tuple(cols), L, predicate, agg_fn, stream_chunks
    )
    col_sh, win_sh, cnt_sh = placements(mesh)
    dev_cols = {k: jax.device_put(v, col_sh) for k, v in cols.items()}
    return fn(
        dev_cols,
        jax.device_put(starts.astype(np.int32), win_sh),
        jax.device_put(ends.astype(np.int32), win_sh),
        jax.device_put(counts.astype(np.int32), cnt_sh),
    )
