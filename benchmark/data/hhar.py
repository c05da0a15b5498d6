"""HHAR-shaped phone and watch accelerometer tables, made from a seed.

The shapes of the UCI HHAR accelerometer files as the reference
quickstart reads them: one row per reading with ``User`` (the letters
the dataset gives its users), ``event_ts`` (the arrival time, whole
milliseconds) and ``x``, ``y``, ``z``.  The phone table holds
``phone_rows`` and the watch table ``watch_rows``, split as evenly as
the counts allow over the users.  Each user's phone readings arrive at
``phone_rate_hz`` (the user's eight phones together), which fixes how
long the user's recording lasts; the watch readings fall over the same
span.  Arrival times are uniform whole milliseconds within the span, so
readings share a millisecond as they do in the dataset.  Every seed
gives the same sizes and spans; the seed draws the times and values.
Values are gravity, spread over the three axes by a per-user
orientation, plus Gaussian motion.
"""

import numpy as np
import pandas as pd

GRAVITY = 9.80665


def _counts(total: int, users: int) -> np.ndarray:
    """Rows per user: ``total`` split as evenly as it goes."""
    per, extra = divmod(total, users)
    return np.full(users, per, dtype=np.int64) + (np.arange(users) < extra)


def span_ms(config: dict) -> np.ndarray:
    """Length of each user's recording, in ms."""
    phone = _counts(int(config["phone_rows"]), len(config["users"]))
    return -(-phone * 1000 // int(config["phone_rate_hz"]))


def _side(rng, users, counts, spans, start_ns, config, table):
    keys = np.repeat(np.asarray(users, dtype=object), counts)
    ms = np.concatenate([np.sort(rng.integers(0, s, size=n))
                         for n, s in zip(counts, spans)])
    n = len(ms)
    orient = rng.normal(size=(len(users), 3))
    orient /= np.linalg.norm(orient, axis=1, keepdims=True)
    xyz = (GRAVITY * np.repeat(orient, counts, axis=0)
           + rng.normal(0.0, float(config["motion_sd"]), size=(n, 3)))
    frame = {table["partition"][0]: keys,
             table["ts"]: pd.to_datetime(start_ns + ms * np.int64(1_000_000))}
    for i, col in enumerate(config["values"]):
        frame[col] = xyz[:, i]
    return pd.DataFrame(frame)


def make(config: dict, rng: np.random.Generator) -> dict:
    """{left table name: phone frame, right table name: watch frame}."""
    left_t, right_t = config["tables"]["left"], config["tables"]["right"]
    users = list(config["users"])
    spans = span_ms(config)
    start_ns = pd.Timestamp(config["start"]).value
    phone = _side(rng, users, _counts(int(config["phone_rows"]), len(users)),
                  spans, start_ns, config, left_t)
    watch = _side(rng, users, _counts(int(config["watch_rows"]), len(users)),
                  spans, start_ns, config, right_t)
    return {left_t["name"]: phone, right_t["name"]: watch}


def series_keys(config: dict) -> np.ndarray:
    """Every partition key the tables hold."""
    return np.asarray(config["users"], dtype=object)
