"""BASELINE.md config 5: WAL replay into the multi-chip ShardedDar on
an 8-device mesh (virtual CPU here; the driver separately dry-runs the
multi-chip path), then sharded conflict-query throughput.

  python benchmarks/bench_sharded_replay.py
Env: DSS_BENCH_OPS (10000), DSS_BENCH_BATCH (512), DSS_BENCH_REPS (8),
     DSS_BENCH_MESH ("2,4")
"""

from __future__ import annotations

import os
import sys
import time
import uuid

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# force the 8-virtual-device CPU mesh BEFORE any jax backend init:
# this script drives the dp x sp sharding program, which no 1- or
# 4-chip host can hold; its output says backend: cpu
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()

import dss_tpu.ops.conflict  # noqa: F401,E402 — x64 before jax init
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from benchmarks._common import emit  # noqa: E402


def main():
    n_ops = int(os.environ.get("DSS_BENCH_OPS", 10_000))
    batch = int(os.environ.get("DSS_BENCH_BATCH", 512))
    reps = int(os.environ.get("DSS_BENCH_REPS", 8))
    dp, sp = (
        int(x) for x in os.environ.get("DSS_BENCH_MESH", "2,4").split(",")
    )

    import tempfile
    from datetime import datetime, timezone

    from dss_tpu.dar import codec
    from dss_tpu.dar.wal import WriteAheadLog
    from dss_tpu.models import scd as scdm
    from dss_tpu.parallel import make_mesh
    from dss_tpu.parallel.replica import ShardedReplica

    rng = np.random.default_rng(0)
    now_dt = datetime.now(timezone.utc)
    now_ns = int(now_dt.timestamp() * 1e9)

    # synthesize the WAL a long-lived standalone server would have:
    # n_ops scd_op_put records over a metro cell space
    n_cells = 20_000
    from dss_tpu.geo import s2cell

    # real level-13 cells around a metro so dar-key compression applies
    base_cell = s2cell.cell_id_from_latlng(40.0, -100.0, level=13)
    # walk a contiguous ij window of the metro's face
    face, i0, j0, size = s2cell.cell_ij_bounds(np.uint64(base_cell))
    side = int(np.sqrt(n_cells))
    ii = np.arange(side) * int(size) + int(i0)
    jj = np.arange(side) * int(size) + int(j0)
    cell_grid = s2cell.cell_parent(
        s2cell.from_face_ij(
            int(face),
            np.repeat(ii, side) + int(size) // 2,
            np.tile(jj, side) + int(size) // 2,
        ),
        13,
    )

    tmp = tempfile.mkdtemp(prefix="dss-bench-")
    wal_path = os.path.join(tmp, "dss.wal")
    wal = WriteAheadLog(wal_path)
    hour = 3_600_000_000_000
    t_build0 = time.perf_counter()
    for k in range(n_ops):
        picks = cell_grid[
            rng.integers(0, len(cell_grid), 6)
        ].astype(np.uint64)
        alt0 = float(rng.uniform(0, 3000))
        t0 = now_ns + int(rng.integers(-2, 3)) * hour
        op = scdm.Operation(
            id=str(uuid.uuid4()),
            owner=f"uss{k & 255}",
            version=1,
            start_time=datetime.fromtimestamp(
                t0 / 1e9, tz=timezone.utc
            ),
            end_time=datetime.fromtimestamp(
                (t0 + 2 * hour) / 1e9, tz=timezone.utc
            ),
            altitude_lower=alt0,
            altitude_upper=alt0 + 300.0,
            cells=picks,
            uss_base_url="https://uss.example.com",
            subscription_id=str(uuid.uuid4()),
            state="Accepted",
            ovn=f"ovn-{k}",
        )
        wal.append({"t": "scd_op_put", "doc": codec.op_to_doc(op)})
    wal.close()
    wal_write_s = time.perf_counter() - t_build0

    mesh = make_mesh(dp * sp, dp=dp, sp=sp)
    rep = ShardedReplica(mesh, wal_path=wal_path)
    t0 = time.perf_counter()
    applied = rep.poll_once()
    ingest_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    rep.refresh()  # build + swap + warm compile
    build_s = time.perf_counter() - t0
    assert applied == n_ops

    # query throughput on the sharded snapshot
    def make_batch(seed):
        r = np.random.default_rng(seed)
        keys = s2cell.cell_to_dar_key(
            cell_grid[r.integers(0, len(cell_grid), (batch, 8))].astype(
                np.uint64
            )
        ).astype(np.int32)
        alo = r.uniform(0, 3000, batch).astype(np.float32)
        t0q = now_ns + r.integers(-1, 2, batch) * hour
        return (
            keys,
            alo,
            (alo + 300.0).astype(np.float32),
            t0q.astype(np.int64),
            (t0q + hour).astype(np.int64),
        )

    dar = rep._snapshots["ops"][0]  # the raw ShardedDar (device leg)
    assert dar is not None
    qb = make_batch(99)
    dar.query_batch(*qb, now=now_ns)  # compile this batch shape
    t0 = time.perf_counter()
    hits = 0
    for i in range(reps):
        out = dar.query_batch(*make_batch(100 + i), now=now_ns)
        hits += sum(len(x) for x in out)
    dt = time.perf_counter() - t0
    qps = batch * reps / dt

    rep.close()
    emit(
        "sharded_replay_query_qps",
        qps,
        "queries/s",
        None,
        {
            "ops": n_ops,
            "mesh": f"{dp}x{sp}",
            "backend": jax.devices()[0].platform,
            "wal_write_s": round(wal_write_s, 2),
            "wal_ingest_s": round(ingest_s, 2),
            "snapshot_build_s": round(build_s, 2),
            "batch": batch,
            "reps": reps,
            "hits_per_query": round(hits / (batch * reps), 1),
            "path": "WAL tail -> ShardedReplica -> shard_map query",
        },
    )


if __name__ == "__main__":
    main()
