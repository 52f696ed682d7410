"""Driver ``sim``: the lookup simulator, a closed loop of waves through
the public ``core.search.simulate_lookups`` over a table of ids made on
the device from the seed — the call ``chip_smoke.phase_simulator`` makes
(``benchmarks/baseline_configs.py`` config 3), one wave in flight.

``setup`` -> state, ``window(state, seconds)`` -> result, ``check(state,
result)`` -> (correct, why), ``close(state)``; see dhtbench/README.md."""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from dhtbench import reference
from dhtbench.trace_reduce import WINDOW_SPAN

ID_BYTES = 20            # a reply entry is one 160-bit id


def least_bytes(total_hops: int, alpha: int, k: int) -> int:
    """The bytes a Kademlia lookup cannot avoid reading: every hop reads
    α replies of k ids of 160 bits.  The shape function of the reply
    gather, for ``sim_gather_hbm_share``."""
    return int(total_hops) * alpha * k * ID_BYTES


def setup(config: dict, traffic: dict, seed: int, log) -> SimpleNamespace:
    import jax
    import jax.numpy as jnp
    from opendht_tpu.core.search import simulate_lookups
    from opendht_tpu.ops.sorted_table import (build_prefix_lut,
                                              default_lut_bits, sort_table)
    sizes = config["sizes"]
    n_ids, n_sets = sizes["n_ids"], traffic["target_sets"]
    n_targets = traffic["wave_targets"]

    @jax.jit
    def make(k_ids, k_targets):
        """ids and every target set in ONE executable, on the device."""
        return (jax.random.bits(k_ids, (n_ids, 5), dtype=jnp.uint32),
                tuple(jax.random.bits(jax.random.fold_in(k_targets, i),
                                      (n_targets, 5), dtype=jnp.uint32)
                      for i in range(n_sets)))

    # --seed runs past 2**31 and jax keys take 32 bits
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    table, sets = make(*jax.random.split(key))
    sorted_ids, _perm, n_valid = jax.block_until_ready(sort_table(table))
    del table
    lut = jax.block_until_ready(build_prefix_lut(
        sorted_ids, n_valid, bits=default_lut_bits(n_ids)))
    if int(n_valid) != n_ids:
        raise RuntimeError(f"{int(n_valid)} valid rows of {n_ids}")
    log(f"sim: {n_ids} ids sorted, LUT built, {n_sets} sets of {n_targets}")

    st = SimpleNamespace(
        config=config, sorted_ids=sorted_ids, n_valid=n_valid, lut=lut,
        sets=sets, base_seed=(seed & 0x3FFFFFFF) + 2, waves_run=0, last=None)

    def wave(i: int):
        """Wave ``i``: its target set in turn, and a reply seed of its own
        (a traced argument of the jit, so a new value compiles nothing)."""
        out = simulate_lookups(
            sorted_ids, n_valid, sets[i % n_sets], seed=st.base_seed + i,
            k=sizes["k"], alpha=sizes["alpha"],
            search_nodes=sizes["search_nodes"], lut=lut,
            state_limbs=sizes["state_limbs"])
        return jax.block_until_ready(out)

    st.wave = wave
    for i in (1, 2):                      # compile, then one warm wave
        t0 = time.perf_counter()
        wave(-i)
        log(f"sim: warm-up wave {i} {time.perf_counter() - t0:.3f}s "
            "(set-up figure)")
    return st


def window(st, seconds: float) -> dict:
    import jax
    wave_ms, outs = [], []
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        t_start = time.perf_counter()
        t_now = t_start
        while t_now - t_start < seconds:
            out = st.wave(st.waves_run)
            st.waves_run += 1
            t_done = time.perf_counter()
            wave_ms.append((t_done - t_now) * 1e3)
            # small per-wave arrays only: holding every wave's nodes and
            # distances would grow device memory by 12 MB a wave
            outs.append((out["converged"], out["hops"]))
            t_now = t_done
        window_s = t_now - t_start
    # reduced after the window: the loop itself fetches nothing
    converged = sum(int(np.asarray(c).sum()) for c, _ in outs)
    hops = np.concatenate([np.asarray(h) for _, h in outs])
    deepest: dict = {}                    # a wave runs its deepest lookup's rounds
    for ms, (_, h) in zip(wave_ms, outs):
        deepest.setdefault(int(np.asarray(h).max()), []).append(ms)
    attempted = hops.shape[0]
    st.last = (st.waves_run - 1, out)
    sizes = st.config["sizes"]
    return {
        "window_s": window_s, "attempted": attempted,
        "failed": attempted - converged,
        "end_to_end": {
            "sim_lookups_per_s": converged / window_s,
            "sim_wave_p90_ms": float(np.percentile(wave_ms, 90))},
        "values": {
            "waves": len(wave_ms), "wave_ms_mean": float(np.mean(wave_ms)),
            "wave_ms_p50": float(np.median(wave_ms)),
            "hops_min": int(hops.min()), "hops_max": int(hops.max()),
            "hops_mean": float(hops.mean()),
            "wave_ms_by_deepest_hops": {h: [len(ms), round(float(np.mean(ms)), 3)]
                                        for h, ms in sorted(deepest.items())},
            "least_bytes": least_bytes(int(hops.sum()), sizes["alpha"],
                                       sizes["k"])}}


def check(st, result: dict):
    """Every lookup converged, hops in range, nothing compiled in the
    window, and a seeded sample of the last wave's closest-k sets equals
    the numpy XOR top-k over the same ids at the guaranteed rate."""
    g, sizes = st.config["guarantees"], st.config["sizes"]
    v = result["values"]
    if v.get("compiles_in_window"):
        raise RuntimeError(f"{v['compiles_in_window']} executable(s) were "
                           "built inside the measured window")
    if result["failed"]:
        return False, f"{result['failed']} lookups did not converge"
    if not g["hops_min"] <= v["hops_min"] <= v["hops_max"] <= g["hops_max"]:
        return False, f"hops {v['hops_min']}..{v['hops_max']} out of range"
    i, out = st.last
    targets = np.asarray(st.sets[i % len(st.sets)])
    nodes = np.asarray(out["nodes"])
    ids = np.asarray(st.sorted_ids)
    if not ((nodes >= 0) & (nodes < ids.shape[0])).all():
        return False, "node rows out of range"
    rng = np.random.default_rng([st.base_seed, i])
    sample = rng.choice(targets.shape[0], replace=False,
                        size=min(g["sample"], targets.shape[0]))
    t0 = time.perf_counter()
    index = reference.XorIndex(ids)
    agree = sum(set(nodes[j].tolist())
                == set(index.closest(targets[j], sizes["k"]).tolist())
                for j in sample)
    floor = int(np.ceil(g["min_exact_agree"] * len(sample)))
    return agree >= floor, (
        f"wave {i}: {agree}/{len(sample)} sampled closest-{sizes['k']} sets "
        f"equal the numpy XOR top-{sizes['k']} (floor {floor}), hops "
        f"{v['hops_min']}..{v['hops_max']} mean {v['hops_mean']:.2f}, "
        f"reference {time.perf_counter() - t0:.1f}s")


def close(st) -> None:
    st.sets = st.sorted_ids = st.lut = st.last = None
