"""Driver ``sim_tp``: the lookup simulator over a table ROW-SHARDED over
the chips of one host — a closed loop of waves through the public
``parallel.tp_simulate_lookups`` on ``make_mesh(q·t, q=, t=)``, the call
``benchmarks/baseline_configs.py config3_tp`` and
``chip_smoke.phase_four_chips`` make, one wave in flight.

Nothing of table size is ever on one device or on the host: the ids are
made ON each shard from the seed (``fold_in`` by shard), the table is
built across the mesh by ``parallel.sharded_global_sort``, and the
check reads it back one shard at a time (``reference_blocks``).  The
wave loop, the result keys and the shape function are those of
``drivers/sim.py``; ``least_bytes`` is PER CHIP here (the total over
``mesh_t``), since a trace's busy time is the mean of the chips'.

``setup`` -> state, ``window(state, seconds)`` -> result, ``check(state,
result)`` -> (correct, why), ``close(state)``; see dhtbench/README.md."""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from dhtbench import reference_blocks
from dhtbench.drivers import sim
# at import, not in setup: a program without the build (the parent of
# the PR that brought it) fails here, before it reaches for the chips
from opendht_tpu import telemetry
from opendht_tpu.parallel import (make_mesh, sharded_global_sort,
                                  tp_simulate_lookups)

BUILD_SPAN = "dht_table_build_seconds"      # the program's, one series a phase


def _mix(x):
    """murmur3's 32-bit finalizer, on uint32 (wrapping) arrays."""
    import jax.numpy as jnp
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def checksum(ids, valid):
    """An order-free fingerprint of the valid rows of ``ids`` [n,5], as
    seven uint32 that add up (wrapping) over any split of the rows: the
    row count, the sum of each limb, and the sum of a hash that chains
    a row's five limbs — so a row lost, doubled or with limbs swapped
    between rows changes it, and the order of the rows does not."""
    import jax.numpy as jnp
    h = ids[:, 0]
    for limb in range(1, 5):
        h = _mix(h ^ ids[:, limb]) + jnp.uint32(limb)
    parts = [valid.astype(jnp.uint32), *(ids[:, l] for l in range(5)), _mix(h)]
    return jnp.stack([jnp.sum(jnp.where(valid, p, jnp.uint32(0)),
                              dtype=jnp.uint32) for p in parts])


def _programs(mesh, n_ids: int, n_targets: int, n_sets: int):
    """The benchmark's own device programs: ids and their checksum made
    on each shard, the target sets, and the read-back of a built table
    (per-shard order, edge rows and checksum)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P
    n_t = mesh.shape["t"]
    rows = -(-n_ids // n_t)

    def make_local(key):
        ti = lax.axis_index("t")
        ids = jax.random.bits(jax.random.fold_in(key, ti), (rows, 5),
                              dtype=jnp.uint32)
        valid = ti * rows + jnp.arange(rows, dtype=jnp.int32) < n_ids
        return ids, valid, lax.psum(checksum(ids, valid), "t")

    make_ids = jax.jit(jax.shard_map(
        make_local, mesh=mesh, in_specs=(P(),),
        out_specs=(P("t", None), P("t"), P()), check_vma=False))

    @jax.jit
    def make_sets(key):
        return tuple(lax.with_sharding_constraint(
            jax.random.bits(jax.random.fold_in(key, i), (n_targets, 5),
                            dtype=jnp.uint32), NamedSharding(mesh, P()))
            for i in range(n_sets))

    def read_local(table, shard_rows):
        width = shard_rows[0, 1]
        cap = table.shape[0]
        at = jnp.arange(cap - 1, dtype=jnp.int32)
        le = table[:-1, 4] <= table[1:, 4]
        for limb in (3, 2, 1, 0):
            a, b = table[:-1, limb], table[1:, limb]
            le = (a < b) | ((a == b) & le)
        ascending = jnp.all(le | (at + 1 >= width))
        valid = jnp.arange(cap, dtype=jnp.int32) < width
        return (ascending[None], table[0][None],
                table[jnp.maximum(width - 1, 0)][None],
                lax.psum(checksum(table, valid), "t"))

    read_table = jax.jit(jax.shard_map(
        read_local, mesh=mesh, in_specs=(P("t", None), P("t", None)),
        out_specs=(P("t"), P("t", None), P("t", None), P()),
        check_vma=False))
    return make_ids, make_sets, read_table


def setup(config: dict, traffic: dict, seed: int, log) -> SimpleNamespace:
    import jax
    sizes = config["sizes"]
    n_ids, n_sets = sizes["n_ids"], traffic["target_sets"]
    mesh = make_mesh(sizes["mesh_q"] * sizes["mesh_t"], q=sizes["mesh_q"],
                     t=sizes["mesh_t"])
    make_ids, make_sets, read_table = _programs(
        mesh, n_ids, traffic["wave_targets"], n_sets)

    # --seed runs past 2**31 and jax keys take 32 bits
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    k_ids, k_targets = jax.random.split(key)
    t0 = time.perf_counter()
    ids, valid, made_sum = jax.block_until_ready(make_ids(k_ids))
    sets = jax.block_until_ready(make_sets(k_targets))
    log(f"sim_tp: {n_ids} ids made on {mesh.shape['t']} shards, {n_sets} "
        f"sets of {traffic['wave_targets']}, {time.perf_counter() - t0:.2f}s")

    registry = telemetry.get_registry()
    before = registry.snapshot()
    state = sharded_global_sort(mesh, ids, valid, donate=True)
    del ids, valid
    spans = telemetry.snapshot_diff(before, registry.snapshot())["histograms"]
    phases = {name.split('"')[1]: h["sum"] for name, h in spans.items()
              if name.startswith(BUILD_SPAN + "{phase=")}
    shard_rows = np.asarray(state.arrays["shard_rows"])
    if int(state.arrays["n_valid"]) != n_ids:
        raise RuntimeError(f"{int(state.arrays['n_valid'])} valid rows of "
                           f"{n_ids}")
    log(f"sim_tp: table built across the mesh in {sum(phases.values()):.2f}s "
        f"{ {k: round(v, 3) for k, v in sorted(phases.items())} }; shard "
        f"(base, width) {shard_rows.tolist()}, capacity {state.shard_n}, "
        f"block LUT 2^{state.block_bits}, local LUT 2^{state.lut_bits}")

    st = SimpleNamespace(
        config=config, mesh=mesh, state=state, shard_rows=shard_rows,
        sets=sets, made_sum=made_sum, read_table=read_table,
        table_build_s=sum(phases.values()),
        base_seed=(seed & 0x3FFFFFFF) + 2, waves_run=0, last=None)

    def wave(i: int):
        """Wave ``i``: its target set in turn, and a reply seed of its own
        (a traced argument of the jit, so a new value compiles nothing)."""
        out = tp_simulate_lookups(
            mesh, targets=sets[i % n_sets], state=state,
            seed=st.base_seed + i, k=sizes["k"], alpha=sizes["alpha"],
            search_nodes=sizes["search_nodes"],
            state_limbs=sizes["state_limbs"])
        return jax.block_until_ready(out)

    st.wave = wave
    for i in (1, 2):                      # compile, then one warm wave
        t0 = time.perf_counter()
        wave(-i)
        log(f"sim_tp: warm-up wave {i} {time.perf_counter() - t0:.3f}s "
            "(set-up figure)")
    return st


def window(st, seconds: float) -> dict:
    """The wave loop of ``drivers/sim.py``, its result keys and its shape
    function; ``least_bytes`` divided over the chips that share the
    table, and the program's build span beside them."""
    result = sim.window(st, seconds)
    values = result["values"]
    values["least_bytes"] //= st.config["sizes"]["mesh_t"]
    values["table_build_s"] = st.table_build_s
    return result


def _shards(st):
    """``(base, rows)`` of each shard's valid rows in the global order,
    fetched one shard at a time."""
    pieces = sorted(st.state.sorted_ids.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    for (base, width), piece in zip(st.shard_rows.tolist(), pieces):
        yield base, np.asarray(piece.data)[:width]


def _built_right(st, n_ids: int) -> "str | None":
    """The ``sorted`` guarantee; ``None`` where it holds, else what broke."""
    ascending, first, last, built_sum = (
        np.asarray(x) for x in st.read_table(st.state.sorted_ids,
                                             st.state.arrays["shard_rows"]))
    if not ascending.all():
        return f"shard rows not ascending: {ascending.tolist()}"
    at = 0
    edge = None                           # the last id of the shards so far
    for i, (base, width) in enumerate(st.shard_rows.tolist()):
        if base != at or not 0 <= width <= st.state.shard_n:
            return f"shard {i} holds rows {base}+{width}, expected from {at}"
        at += width
        if width:
            if edge is not None and tuple(first[i]) < edge:
                return f"shard {i} starts below the end of the shard before"
            edge = tuple(last[i])
    if at != n_ids:
        return f"the shards hold {at} rows of {n_ids}"
    made_sum = np.asarray(st.made_sum)
    if not np.array_equal(built_sum, made_sum):
        return (f"checksum of the built table {built_sum.tolist()} is not "
                f"the seed's {made_sum.tolist()}")
    return None


def check(st, result: dict):
    """The guarantees of ``sim-10m`` — every lookup converged, hops in
    range, nothing compiled in the window, a seeded sample of the last
    wave's closest-k sets equal to the numpy XOR top-k over ALL the ids
    at the guaranteed rate — and ``sorted``: the built table is globally
    ordered and is the seed's multiset."""
    g, sizes = st.config["guarantees"], st.config["sizes"]
    v = result["values"]
    if v.get("compiles_in_window"):
        raise RuntimeError(f"{v['compiles_in_window']} executable(s) were "
                           "built inside the measured window")
    if result["failed"]:
        return False, f"{result['failed']} lookups did not converge"
    if not g["hops_min"] <= v["hops_min"] <= v["hops_max"] <= g["hops_max"]:
        return False, f"hops {v['hops_min']}..{v['hops_max']} out of range"
    t0 = time.perf_counter()
    broke = _built_right(st, sizes["n_ids"])
    if broke:
        return False, "table: " + broke
    built_s = time.perf_counter() - t0
    i, out = st.last
    targets = np.asarray(st.sets[i % len(st.sets)])
    nodes = np.asarray(out["nodes"])
    if not ((nodes >= 0) & (nodes < sizes["n_ids"])).all():
        return False, "node rows out of range"
    rng = np.random.default_rng([st.base_seed, i])
    sample = rng.choice(targets.shape[0], replace=False,
                        size=min(g["sample"], targets.shape[0]))
    t0 = time.perf_counter()
    exact = reference_blocks.closest_over_blocks(
        _shards(st), targets[sample], sizes["k"])
    agree = sum(set(nodes[j].tolist()) == set(rows.tolist())
                for j, rows in zip(sample, exact))
    floor = int(np.ceil(g["min_exact_agree"] * len(sample)))
    return agree >= floor, (
        f"wave {i}: {agree}/{len(sample)} sampled closest-{sizes['k']} sets "
        f"equal the numpy XOR top-{sizes['k']} over {sizes['n_ids']} ids "
        f"read in {st.shard_rows.shape[0]} blocks (floor {floor}), hops "
        f"{v['hops_min']}..{v['hops_max']} mean {v['hops_mean']:.2f}; table "
        f"ascending on every shard and across them, checksum "
        f"{np.asarray(st.made_sum).tolist()} equals the seed's "
        f"({built_s:.1f}s); reference {time.perf_counter() - t0:.1f}s")


def close(st) -> None:
    st.sets = st.state = st.last = st.wave = None
