"""Milliseconds a Get spent placing reply rows into the caller's buffer
(Dashboard CLIENT_PLACE_ROWS's milliseconds over the Get replies the
worker handled, WORKER_REPLY_GET's count; measured window, profiler
off). A reply in several shards places once a shard."""


def read(obs):
    placed = obs.window.counters.get("CLIENT_PLACE_ROWS", {})
    replies = obs.window.counters.get("WORKER_REPLY_GET", {})
    if not placed.get("count") or not replies.get("count"):
        return None
    return placed["ms"] / replies["count"]
