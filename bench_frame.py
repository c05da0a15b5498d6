"""Frame-level end-to-end benchmark at quickstart scale.

Times what a user actually calls — pandas in -> ``TSDF.on_mesh`` ->
``asofJoin`` -> ``withRangeStats`` -> ``EMA`` -> ``collect`` — on the
HHAR-shaped workload of ``tempo_tpu.testing.hhar`` (the reference
quickstart's 13,062,475-row phone<->watch accelerometer join,
`Tempo QuickStart - Python.ipynb` cell 3), reporting the three phases
separately:

* ``t_pack``   — host packing + upload (``on_mesh`` + a forcing probe);
* ``t_device`` — the full op chain on-device, forced by fetching a
  data-dependent scalar;
* ``t_fetch``  — ``collect()``: ONE stacked device->host transfer plus
  host assembly back to pandas.

``rows_per_sec_device`` is the chain's rate, ``rows_per_sec_end_to_end``
includes the host phases.  Scale with TEMPO_BENCH_FRAME_ROWS (default
the full 13M; CI smoke uses ~100k).

Prints ONE json line.
"""

import json
import os
import sys
import time

import tempo_tpu  # noqa: F401
import jax
import jax.numpy as jnp

from tempo_tpu import TSDF
from tempo_tpu.parallel import make_mesh
from tempo_tpu.testing.hhar import HHAR_ROWS, make_frames

N_ROWS = int(os.environ.get("TEMPO_BENCH_FRAME_ROWS", HHAR_ROWS))


def main():
    left, right, n = make_frames(N_ROWS)
    mesh = make_mesh({"series": len(jax.devices())})

    t0 = time.perf_counter()
    dl = TSDF(left, "event_ts", ["user"]).on_mesh(mesh)
    dr = TSDF(right, "event_ts", ["user"]).on_mesh(mesh)
    # force the uploads: a data-dependent scalar fetch
    float(jnp.asarray(dl.ts).sum() + jnp.asarray(dr.ts).sum())
    t_pack = time.perf_counter() - t0

    def chain():
        t0 = time.perf_counter()
        out = (
            dl.asofJoin(dr)
            .withRangeStats(colsToSummarize=["x"], rangeBackWindowSecs=10)
            .EMA("x", exact=True)
        )
        # force the whole chain without fetching the planes
        float(jnp.nan_to_num(out.cols["EMA_x"].values).sum()
              + jnp.nan_to_num(out.cols["mean_x"].values).sum()
              + jnp.nan_to_num(out.cols["right_wx"].values).sum())
        return out, time.perf_counter() - t0

    out, t_device = chain()          # cold: includes jit compiles
    _, t_device_warm = chain()       # warm: compiled programs cached

    t0 = time.perf_counter()
    df = out.collect().df
    t_fetch = time.perf_counter() - t0
    assert len(df) == n, (len(df), n)

    fetched_mb = sum(
        df[c].to_numpy().nbytes for c in df.columns
    ) / 1e6
    print(json.dumps({
        "metric": "frame-level pandas->mesh->asofJoin+rangeStats+EMA->collect",
        "rows": n,
        "t_pack_s": round(t_pack, 2),
        "t_device_s": round(t_device, 2),
        "t_device_warm_s": round(t_device_warm, 2),
        "t_fetch_s": round(t_fetch, 2),
        "rows_per_sec_device": round(n / t_device_warm),
        "rows_per_sec_end_to_end": round(n / (t_pack + t_device + t_fetch)),
        "collect_mb": round(fetched_mb),
    }))


if __name__ == "__main__":
    sys.exit(main())
