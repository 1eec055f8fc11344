"""Pallas-fused fleet round step: one whole `energy.step_ops` program per
client tile in VMEM.

The lax backend's round is a dozen separate elementwise ``(N,)`` ops — at
1e7+ clients each intermediate (available, mask, consumed, depleted, ...)
round-trips through HBM.  This kernel runs the ENTIRE step program
(`step_ops.apply_ops` — the same op closures the lax backend executes) over
one client tile per grid step: every per-client input is read from HBM
once, every intermediate lives in VMEM, and only the carried state (charge)
plus, optionally, the recorded mask/mode are written back — one HBM read +
one write of the fleet per round, the roofline lower bound modeled by
`step_ops.bytes_moved`.

Telemetry fuses too: each grid step reduces its tile's valid-weighted stat
buffers to one row of a ``(tiles, 1, S)`` partial-sum output; the wrapper sums
rows (and `lax.psum`s across shards) before forming the masked averages, so
the kernel never materializes a per-client stat buffer in HBM.

Tile/grid rule (DESIGN.md §11): the client axis is zero-padded up to a
multiple of the tile (``tiles = ceil(n / tile)``) and the tail tile is
masked — ``valid`` is zero-padded alongside, so padded lanes contribute
nothing to any partial sum, and per-client outputs are sliced back to
``n``.  Zero (not edge) padding is safe INSIDE the kernel because the step
programs guard every division (`serve_drain`'s ``max(per_req, 1e-20)``);
the mesh-level edge padding of `energy.fleet._pad_clients` still happens
outside, before the kernel sees the arrays.

Sharding: `fused_step_sharded` wraps the kernel in a
``jax.shard_map(check_vma=False)`` over the mesh's data axes — each shard runs
the tile grid over its local client slab (the per-shard slab is re-padded
to a tile multiple by the same rule) and the stat partials are ``psum``-ed
before the averages are formed.  RNG-bearing inputs (harvest / requests /
SUSTAINABLE want) are computed OUTSIDE under GSPMD jit with global client
indices, so the per-client RNG contract is untouched by the kernel
boundary.

Interpret mode follows `kernels.platform`: compiled when the program is
lowered for a TPU, interpreted elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as dist_sharding
from repro.energy import step_ops
from repro.kernels.platform import pallas_call
from repro.obs import hist as hist_lib

DEFAULT_TILE = 65536


def _tile_for(n: int, tile: int | None) -> int:
    """Tile rule: DEFAULT_TILE, or for small fleets the next power of two
    >= n (floor 8) so the grid is a single masked tile."""
    if tile is not None:
        return tile
    if n >= DEFAULT_TILE:
        return DEFAULT_TILE
    return max(8, 1 << max(n - 1, 1).bit_length())


def _env_names(program: step_ops.StepProgram,
               num_groups: int | None) -> tuple[str, ...]:
    """Kernel input buffers, in deterministic first-use order: the program's
    consumed-not-written buffers plus the reduction weights."""
    names = list(program.input_names()) + ["valid"]
    if num_groups:
        names.append("groups")
    return tuple(names)


def _stat_names(program: step_ops.StepProgram,
                num_groups: int | None) -> tuple[str, ...]:
    names = [s for s, _ in program.totals + program.averages]
    if num_groups:
        names += [s for s, _ in program.group_totals
                  + program.group_averages]
    names += [spec.name for spec in program.hists]
    return tuple(names)


def _partials_width(program: step_ops.StepProgram,
                    num_groups: int | None) -> int:
    """Layout of one partial-sum row: [totals][average numerators][sum of
    valid], then per group g: [group totals][group numerators][sum of w_g],
    then per histogram spec: [bin counts] (bins entries each)."""
    base = len(program.totals) + len(program.averages) + 1
    if num_groups:
        base += num_groups * (len(program.group_totals)
                              + len(program.group_averages) + 1)
    base += sum(spec.bins for spec in program.hists)
    return base


def _lanes(width: int) -> int:
    """Partial-sum row width padded to whole 128-lane vregs."""
    return -(-width // 128) * 128


def _make_kernel(program: step_ops.StepProgram, names: tuple[str, ...],
                 emit: bool, num_groups: int | None):
    n_in = len(names)
    lanes = _lanes(_partials_width(program, num_groups))

    def kernel(*refs):
        env = {nm: refs[i][...] for i, nm in enumerate(names)}
        env = step_ops.apply_ops(program.ops, env)
        out_refs = refs[n_in:]
        k = 0
        for nm in program.state_out:
            out_refs[k][...] = env[nm]
            k += 1
        if emit:
            for nm in program.emit:
                out_refs[k][...] = env[nm]
                k += 1
        valid = env["valid"]
        # tile partial sums, in the `_partials_width` layout; `valid * v` is
        # the exact `collectives.masked_total` product order
        parts = [jnp.sum(valid * env[buf].astype(jnp.float32))
                 for _, buf in program.totals + program.averages]
        parts.append(jnp.sum(valid))
        if num_groups:
            for g in range(num_groups):
                wg = valid * (env["groups"] == g).astype(jnp.float32)
                parts += [jnp.sum(wg * env[buf].astype(jnp.float32))
                          for _, buf in program.group_totals
                          + program.group_averages]
                parts.append(jnp.sum(wg))
        # per-tile histogram partials: bin with the SAME `hist.bin_index`
        # expression as the lax backend, then one valid-weighted indicator
        # sum per bin — {0, 1} summands, so tile partials are exact integers
        # and reassociate bit-exactly across tiles/shards
        for spec in program.hists:
            idx = hist_lib.bin_index(env[spec.buf], spec.lo, spec.hi,
                                     spec.bins)
            parts += [jnp.sum(valid * (idx == b).astype(jnp.float32))
                      for b in range(spec.bins)]
        # place each scalar partial in its lane by select (Mosaic cannot
        # concatenate scalars into a vector); unused lanes stay zero
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, lanes), 2)
        row = jnp.zeros((1, 1, lanes), jnp.float32)
        for j, part in enumerate(parts):
            row = jnp.where(lane == j, part, row)
        out_refs[k][...] = row

    return kernel


def _stats_from_partials(program: step_ops.StepProgram, p,
                         num_groups: int | None) -> dict:
    """Partial-sum row -> stats dict, forming the masked averages
    (num / max(den, 1.0), exactly `collectives.masked_average`) only AFTER
    all tile/shard partials are summed."""
    T, A = len(program.totals), len(program.averages)
    stats = {s: p[i] for i, (s, _) in enumerate(program.totals)}
    den = jnp.maximum(p[T + A], 1.0)
    for j, (s, _) in enumerate(program.averages):
        stats[s] = p[T + j] / den
    off = T + A + 1
    if num_groups:
        GT, GA = len(program.group_totals), len(program.group_averages)
        gwidth = num_groups * (GT + GA + 1)
        block = p[off:off + gwidth].reshape(num_groups, GT + GA + 1)
        for k, (s, _) in enumerate(program.group_totals):
            stats[s] = block[:, k]
        gden = jnp.maximum(block[:, GT + GA], 1.0)
        for k, (s, _) in enumerate(program.group_averages):
            stats[s] = block[:, GT + k] / gden
        off += gwidth
    for spec in program.hists:
        stats[spec.name] = p[off:off + spec.bins]
        off += spec.bins
    return stats


def fused_step(program: step_ops.StepProgram, env: dict, *, n: int,
               emit: bool = False, num_groups: int | None = None,
               tile: int | None = None, interpret: bool | None = None,
               axis_name=None) -> tuple[dict, dict, dict]:
    """Run one fused round step over an ``n``-client fleet.

    ``env`` must hold every buffer in ``program.input_names()`` plus
    ``valid`` (and ``groups`` with static ``num_groups``): per-client
    buffers of leading dim ``n`` are tiled over the grid, size-1 buffers are
    broadcast to every tile.  Returns ``(state, emits, stats)`` dicts —
    state/emit buffers sliced back to ``(n,)``, stats fully reduced (via
    ``lax.psum`` over ``axis_name`` when running per-shard under
    `fused_step_sharded`).
    """
    names = _env_names(program, num_groups)
    tile = _tile_for(n, tile)
    n_pad = -(-n // tile) * tile
    tiles = n_pad // tile

    inputs, in_specs = [], []
    for nm in names:
        v = jnp.asarray(env[nm])
        if v.ndim == 1 and v.shape[0] == n:
            if n_pad != n:
                v = jnp.pad(v, (0, n_pad - n))       # zero-pad: masked tail
            in_specs.append(pl.BlockSpec((tile,), lambda i: (i,)))
        elif v.size == 1:
            v = v.reshape(1)
            in_specs.append(pl.BlockSpec((1,), lambda i: (0,)))
        else:
            raise ValueError(
                f"step-op env buffer {nm!r} has shape {v.shape}; expected a "
                f"scalar or a leading client dim of {n}")
        inputs.append(v)

    out_sd = jax.eval_shape(
        lambda e: step_ops.apply_ops(program.ops, e),
        {nm: jax.ShapeDtypeStruct(v.shape, v.dtype)
         for nm, v in zip(names, inputs)})
    out_names = list(program.state_out) + (list(program.emit) if emit else [])
    out_specs = [pl.BlockSpec((tile,), lambda i: (i,)) for _ in out_names]
    out_shape = [jax.ShapeDtypeStruct((n_pad,), out_sd[nm].dtype)
                 for nm in out_names]
    width = _partials_width(program, num_groups)
    # (tiles, 1, lanes) with (1, 1, lanes) blocks: the block's last two dims
    # equal the array's, so every tile count satisfies the TPU tiling rule
    out_specs.append(pl.BlockSpec((1, 1, _lanes(width)), lambda i: (i, 0, 0)))
    out_shape.append(jax.ShapeDtypeStruct((tiles, 1, _lanes(width)),
                                          jnp.float32))

    outs = pallas_call(
        _make_kernel(program, names, emit, num_groups),
        grid=(tiles,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*inputs)

    partials = jnp.sum(outs[-1], axis=(0, 1))[:width]            # (width,)
    if axis_name is not None:
        partials = jax.lax.psum(partials, axis_name)
    state = {nm: outs[i][:n] for i, nm in enumerate(program.state_out)}
    k = len(program.state_out)
    emits = {nm: outs[k + i][:n]
             for i, nm in enumerate(program.emit)} if emit else {}
    return state, emits, _stats_from_partials(program, partials, num_groups)


def fused_step_sharded(program: step_ops.StepProgram, env: dict, *, n: int,
                       mesh, emit: bool = False,
                       num_groups: int | None = None,
                       tile: int | None = None,
                       interpret: bool | None = None
                       ) -> tuple[dict, dict, dict]:
    """`fused_step` composed with the mesh-sharded client axis: each shard
    tiles its local slab (padded n must divide the data-axis product — the
    `simulate_fleet` mesh padding guarantees it) and stat partials are
    psum-ed over the data axes before averaging, so results match the
    host-local kernel bit-for-bit on exact-arithmetic configs."""
    daxes = dist_sharding.data_axes(mesh)
    axis = dist_sharding.mesh_axis_size(mesh, daxes)
    if n % axis:
        raise ValueError(f"fused_step_sharded needs the padded fleet width "
                         f"({n}) to divide the data-axis product ({axis})")
    n_local = n // axis
    lead = daxes if len(daxes) > 1 else daxes[0]
    names = _env_names(program, num_groups)
    env = {nm: jnp.asarray(env[nm]) for nm in names}
    in_specs = ({nm: P(lead) if v.ndim == 1 and v.shape[0] == n else P()
                 for nm, v in env.items()},)
    out_specs = ({nm: P(lead) for nm in program.state_out},
                 {nm: P(lead) for nm in (program.emit if emit else ())},
                 {nm: P() for nm in _stat_names(program, num_groups)})

    def body(e):
        return fused_step(program, e, n=n_local, emit=emit,
                          num_groups=num_groups, tile=tile,
                          interpret=interpret, axis_name=daxes)

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(env)
