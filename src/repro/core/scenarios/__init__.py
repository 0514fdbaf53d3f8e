"""Composable on-device workload generators fused into the fleet scan.

``Scenario`` mirrors ``PolicyFns``: a pure ``(init_fn, chunk_fn)`` pair over
[B]-stacked array params that emits ``[B, chunk]`` observation slabs on
device, deterministically from counter-based PRNG state threaded through
the scan carry.  ``core.fleet.run_fleet(..., scenario=...)`` fuses
generation into the chunked simulation (device memory O(B * chunk), zero
host->device observation transfer) and is bit-identical to materializing
the same scenario and running the classic path.

See ``base`` for the contract, ``streams`` for the migrated generator
families, ``combinators`` for mixtures / regime switching / antithetic
pairing / trace playback.
"""
from repro.core.scenarios.base import (ObsSlab, Scenario, Stream, as_keys,
                                       bcast, materialize, materialize_stream,
                                       shared_keys, slot_keys, slot_uniform,
                                       split_keys)
from repro.core.scenarios.combinators import (antithetic_pairing, combine,
                                              mixture, mixture_from_weights,
                                              regime_switch, replicate_seeds,
                                              tile_services, trace_scenario,
                                              with_seed)
from repro.core.scenarios.streams import (adversarial_evict_bait,
                                          adversarial_fetch_bait, arma_rents,
                                          bernoulli_arrivals, bursty_arrivals,
                                          constant_rents, ge_arrivals,
                                          model2_service, na_rents,
                                          poisson_arrivals, spot_bounds,
                                          spot_rents, trace_arrivals,
                                          trace_rents, uniform_rents)

__all__ = [
    "ObsSlab", "Scenario", "Stream", "as_keys", "bcast",
    "materialize", "materialize_stream", "shared_keys", "slot_keys",
    "slot_uniform", "split_keys",
    "antithetic_pairing", "combine", "mixture", "mixture_from_weights",
    "regime_switch", "replicate_seeds", "tile_services", "trace_scenario",
    "with_seed",
    "adversarial_evict_bait", "adversarial_fetch_bait", "arma_rents",
    "bernoulli_arrivals", "bursty_arrivals", "constant_rents", "ge_arrivals",
    "model2_service", "na_rents", "poisson_arrivals", "spot_bounds",
    "spot_rents", "trace_arrivals", "trace_rents", "uniform_rents",
]
