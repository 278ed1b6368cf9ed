"""Driver for row traffic on one dense matrix table: a closed loop of
one client that makes the round a traffic mix names through the worker
and server actors of one in-process zoo. A mix's ``ops`` are ``get`` and
``add`` (``get_rows`` and ``add_rows`` with host ids and host numpy
buffers) or ``get_device`` and ``add_device`` (``get_rows_device`` and
``add_rows`` with ids, deltas and replies that are ``jax.Array``s: the
same ids and deltas, placed on the device in set-up, and no row's bytes
on the host inside the window)."""

import time

import numpy as np

from benchmark.lib.rowtraffic import RowTraffic
from benchmark.reference import rows_replay


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.config
        self.round_index = 0
        self.log = []          # every acknowledged request, in order
        self.kept = None       # sampled rows of each Get's reply
        self.on_device = None  # what the device operations send, and keep
        self.gets_kept = 0
        self.problems = []     # requests that failed
        self.compared = {}     # what check() compared: [value, limit]

    # -- set-up ---------------------------------------------------------
    def build(self):
        import multiverso_tpu as mv
        c = self.config
        self.traffic = RowTraffic(self.ctx.traffic, c["rows"], c["cols"],
                                  self.ctx.seed)
        mv.init([f"-rpc_timeout_s={self.ctx.deadline_s}"])
        self.table = mv.create_matrix_table(
            c["rows"], c["cols"], dtype=np.dtype(c["dtype"]),
            updater_type=c["updater"])
        n = int(self.ctx.traffic["ids_per_request"])
        self.reply = np.empty((n, c["cols"]), np.dtype(c["dtype"]))
        # Room for the sampled rows of every Get's reply, made once: the
        # loop itself allocates nothing that outlives a round, so the
        # allocator's state in round 1000 is its state in round 10.
        widest = max(p.size for p in self.traffic.positions)
        self.kept = np.empty((int(self.ctx.traffic["gets_checked"]), widest,
                              c["cols"]), self.reply.dtype)
        self.ctx.shapes.update(rows_per_request=n, cols=c["cols"],
                               value_bytes=np.dtype(c["dtype"]).itemsize)
        if any(op.endswith("_device") for op in self.traffic.ops):
            self.on_device = OnDevice(self.traffic, self.kept.shape[0])

    def warm(self):
        for _ in range(int(self.ctx.traffic["warm_rounds"])):
            self._round(None)
        self._sync()

    # -- the loop ---------------------------------------------------------
    def _sync(self):
        """An acknowledged Add has been queued on the device, not run.
        A Get of one row comes back only when everything before it on
        the table has."""
        if self.on_device is None:
            self.table.get_rows(np.zeros(1, np.int32))
        else:
            self.table.get_rows_device(self.on_device.first_row) \
                .block_until_ready()

    def _round(self, window):
        request = self.traffic.request(self.round_index)
        ids = self.traffic.ids[request]
        for op in self.traffic.ops:
            t0 = time.perf_counter()
            try:
                with self.ctx.span(f"{op}_rows"):
                    if op == "get":
                        self.table.get_rows(ids, self.reply)
                    elif op == "add":
                        self.table.add_rows(ids, self.traffic.delta(request))
                    else:
                        op = self.on_device.send(op, self.table, request)
            except Exception as exc:  # noqa: BLE001 - counted, reported
                self.problems.append(f"{op} {len(self.log)}: {exc!r}")
                if window is not None:
                    window.failed += 1
                    window.attempted += 1
                continue
            ms = (time.perf_counter() - t0) * 1e3
            kept = None
            if op == "get" and self.gets_kept < self.kept.shape[0]:
                where = self.traffic.positions[request]
                kept = self.kept[self.gets_kept, :where.size]
                if self.on_device is None:
                    np.take(self.reply, where, axis=0, out=kept)
                else:
                    self.on_device.keep(self.gets_kept, request)
                self.gets_kept += 1
            self.log.append((op, request, kept))
            if window is not None:
                window.attempted += 1
                window.samples.setdefault(f"{op}_ms", []).append(ms)
                window.work["rows"] = window.work.get("rows", 0) + ids.size
        self.round_index += 1

    def measure(self, seconds: float):
        window = self.ctx.open_window()
        deadline = window.t_start + seconds
        while time.monotonic() < deadline:
            self._round(window)
            window.rounds += 1
        self._sync()
        return self.ctx.close_window(window)

    # -- after the window ---------------------------------------------------
    def check(self) -> list:
        if self.on_device is not None:
            self.on_device.copy_out(self.kept)
        final = self.table.get_rows(self.traffic.sample)
        wrong = list(self.problems)
        gets = sum(1 for op, _, _ in self.log if op == "get")
        print(f"[bench] replies compared with the replay: "
              f"{self.gets_kept} of {gets} Gets", flush=True)
        if not np.isfinite(final).all():
            wrong.append("final table: non-finite values")
        differ = rows_replay.replay(self.traffic, self.log, final,
                                    self.config["cols"])
        self.compared = {
            "requests_failed": [len(self.problems), 0],
            "replies_and_tables_that_differ": [len(differ),
                                               rows_replay.TOLERANCE]}
        return wrong + differ

    def close(self):
        import multiverso_tpu as mv
        del self.table
        self.on_device = None
        mv.shutdown()


class OnDevice:
    """The mix's id sets and deltas as ``jax.Array``s, placed once in
    set-up, and the two device operations. The sampled positions of a
    reply are gathered on the device and stay there; after the window
    they are copied out, and ``Driver.kept`` then holds what the host
    operations would have put there."""

    def __init__(self, traffic, gets_kept):
        import jax
        import jax.numpy as jnp
        self.ids = [jax.device_put(ids) for ids in traffic.ids]
        self.deltas = [jax.device_put(d) for d in traffic.deltas]
        # Every request's positions at one length, the same for every
        # seed (no request has more sampled rows than the sample has;
        # the first is repeated), so that one program takes them all
        # and a second process finds it in the compile cache.
        self.positions = [
            jax.device_put(np.concatenate(
                [p, np.full(traffic.sample_most - p.size, p[0], p.dtype)])
                .astype(np.int32)) for p in traffic.positions]
        self.first_row = jnp.zeros(1, jnp.int32)

        def sampled_rows(reply, where):
            return reply[where]
        self._sampled_rows = jax.jit(sampled_rows)
        self.reply = None
        self.kept = [None] * gets_kept
        jax.block_until_ready((self.ids, self.deltas, self.positions))

    def send(self, op, table, request) -> str:
        """Makes the operation, and says which of the host's it stands
        for. A Get ends when its reply is ready on the device; an Add at
        its acknowledgement, as the host's does."""
        if op == "get_device":
            self.reply = table.get_rows_device(self.ids[request])
            self.reply.block_until_ready()
            return "get"
        if op == "add_device":
            table.add_rows(self.ids[request],
                           self.deltas[request % len(self.deltas)])
            return "add"
        raise ValueError(f"unknown operation {op!r}")

    def keep(self, slot, request):
        self.kept[slot] = self._sampled_rows(self.reply,
                                             self.positions[request])

    def copy_out(self, kept):
        for slot, rows in enumerate(self.kept):
            if rows is not None:
                kept[slot] = np.asarray(rows)[:kept.shape[1]]
