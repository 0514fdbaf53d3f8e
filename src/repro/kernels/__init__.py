"""Pallas kernels for the repo's compute hot-spots.

Layout: one ``<name>.py`` per kernel family (``flash_attention``,
``ssd_scan``, ``hosting`` — the row-blocked DP min-plus forward), jitted
public wrappers for the model kernels in ``ops.py``, pure-jnp oracles in
``ref.py``, shared padding/block plumbing in ``utils.py``.

The ``interpret=True``-on-CPU convention
----------------------------------------
Every wrapper takes an ``interpret`` flag, default ``None``, which
``utils.default_interpret`` resolves to True iff ``jax.default_backend()
== "cpu"``.  On CPU (the test/CI platform) there is no Mosaic backend, so
the kernel body runs through the Pallas interpreter as plain XLA ops —
semantically (and for the hosting kernel *bitwise*) identical to the
compiled lowering, but NOT a TPU performance proxy.  On TPU the same call
runs the compiled kernel.

When the engine takes a kernel in place of its XLA recursion (only where
the code observes that the kernel wins, and bitwise equal to it) is set
out in ``docs/CONVENTIONS.md``; how the kernel rows are benchmarked and
gated in ``docs/BENCHMARKS.md``.
"""
