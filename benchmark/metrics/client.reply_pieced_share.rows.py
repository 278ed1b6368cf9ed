"""Percent of a host row Get's reply shards whose payload left the device
in row-range pieces, each placed in the caller's buffer while the next was
still being copied: Dashboard GET_REPLY_ROWS_PIECED over
GET_REPLY_ROWS_PIECED + GET_REPLY_ROWS_WHOLE (one a host row reply shard
`MatrixWorker.process_reply_get` handed its sink: in pieces, or as the one
array it always was), measured window. Under 100 a host Get has left the
form its copy and placement were measured on."""

from benchmark.lib import counters


def read(obs):
    return counters.share(obs.window.counters, "GET_REPLY_ROWS_PIECED",
                          "GET_REPLY_ROWS_WHOLE")
