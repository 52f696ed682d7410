"""CLI tools (↔ reference tools/): dhtnode interactive node/daemon,
dhtchat minimal IM, dhtscanner keyspace census, plus shared argv/identity
helpers (↔ tools/tools_common.h)."""


def force_cpu_jax() -> None:
    """Pin JAX to the CPU backend, before its first use.  The default
    for the CLI tools and the cluster harnesses: a node started from
    the command line has a routing table of tens to hundreds of rows,
    which resolves on the host (``core.table.HOST_SCAN_MAX_ROWS``), and
    a cluster is N such processes — a chip belongs to ONE process, so
    the N-1 others could not start.  Lives HERE — not in tools.common,
    which eagerly imports the crypto-backed runner stack — so
    crypto-free callers (the virtual cluster harness,
    testing/benchmark.py) share the one pinning recipe."""
    import jax
    jax.config.update("jax_platforms", "cpu")


def require_tpu() -> None:
    """``--tpu``: this process gets the chip or does not start.  A node
    asked to serve from the device must never come up on the CPU
    backend unnoticed."""
    import jax
    dev = jax.devices()[0]          # raises when jax cannot start at all
    if dev.platform != "tpu":
        raise SystemExit(f"--tpu: JAX found {dev.platform!r} "
                         f"({dev.device_kind}), not a TPU")
