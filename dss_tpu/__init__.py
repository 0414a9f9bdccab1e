"""dss_tpu — a TPU-native Discovery & Synchronization Service framework.

A ground-up reimplementation of the capabilities of the InterUSS DSS
(reference: /root/reference, Go + CockroachDB): RID Identification
Service Areas + Subscriptions and SCD operational-intent deconfliction,
with the airspace spatial-search / conflict-detection hot path executed
as batched JAX kernels over an HBM-resident DAR (DSS Airspace
Representation) snapshot instead of per-query SQL scans.

Layer map (outside in):

    api/        REST gateway (aiohttp) — routes per the ASTM OpenAPI surface
    auth/       JWT (RS256) auth, scope enforcement, key resolvers
    services/   RID application logic + SCD handlers (fencing, OVN checks,
                notification fanout, quotas)
    dar/        storage: repository seam, in-memory store, TPU-backed store
                (host-authoritative WAL + device DAR snapshot)
    ops/        JAX conflict-query kernels
    parallel/   multi-chip DAR sharding (Mesh/shard_map, ICI collectives)
    geo/        S2 cell geometry (level-13 coverings)
    models/     shared value types (ID, Owner, Version, OVN, Volume4D)
"""

__version__ = "0.1.0"
