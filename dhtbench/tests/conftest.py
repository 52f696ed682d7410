"""The benchmark's own tests run on the CPU backend: a chip belongs to one
process, and what runs on it is the benchmark itself."""

import jax

jax.config.update("jax_platforms", "cpu")
