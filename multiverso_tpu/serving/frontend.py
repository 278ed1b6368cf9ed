"""Online serving frontend: the PS as a read-mostly inference service.

ROADMAP item 4, the "training + serving system" step (docs/SERVING.md):
an HTTP frontend running on worker ranks that turns the parameter
server's versioned, staleness-bounded read path into an inference
surface while a trainer concurrently pushes Adds. Endpoints:

- ``GET /v1/tables``                          — registered tables;
- ``GET /v1/tables/<name>/rows?ids=3,17,42``  — row read;
- ``GET /v1/tables/<name>/neighbors?word=w&k=8`` (or ``id=<row>``)
                                              — word2vec nearest
                                                neighbors by cosine;
- ``GET /v1/status``                          — admission + pressure
                                                (never shed: health
                                                must answer under
                                                overload).

Reads route through the PR-3 client cache (``tables/client_cache.py``:
version tracking, partial row hits, read-your-writes floors) and the
PR-7 replica striping underneath it — the PS itself only sees cache
misses. Every response carries the serving version, its staleness
bound, and a cache-hit marker (JSON fields + ``X-MV-*`` headers); the
reported ``max_staleness <= staleness_bound`` invariant holds even
while Adds land concurrently (``MatrixWorker.read_rows_versioned``).

Survival under load is delegated to ``serving/admission.py``: shed
requests answer ``429/503 + Retry-After`` with the precise
``retry_after_s`` in the JSON body; shutdown drains gracefully.

Built on the shared ``io/http_server.py`` base (the same plumbing as
the observability scrape surface). The frontend itself is runtime-thin:
it holds the zoo only for actor-mailbox pressure probes and never
imports table implementations — tables register by handle
(``mv.serve_table``) and are used duck-typed.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..runtime import thread_roles
from ..io.http_server import (HttpError, HttpServer, Response,
                              json_response)
from ..util import log
from ..util.configure import get_flag
from ..util.dashboard import count as count_event
from ..util.dashboard import samples
# The -serving_* flag definitions live in admission.py (imported
# eagerly by the zoo for parse-time registration; this module pulls in
# the io/ stack and cannot be imported that early).
from .admission import AdmissionController, ShedError
from .ann import IVFIndex
from .batch import BatchedTableReader, HotRowCache, UpstreamReadError

#: Metric names (util/dashboard.py METRIC_NAMES).
REQUESTS = "SERVING_REQUESTS"
LATENCY_MS = "SERVING_LATENCY_MS"
CACHE_HIT = "SERVING_CACHE_HIT"
ANN_PROBE_MS = "ANN_PROBE_MS"

#: Neighbor-endpoint k cap: top-k over the full table is O(rows) per
#: request regardless of k, but an unbounded k makes response bodies
#: a memory lever.
MAX_NEIGHBORS = 64

#: Actor registry names (runtime/actor.py) — plain strings here so the
#: serving package stays runtime-import-free (the zoo imports THIS
#: module eagerly for flag registration; an import back into runtime/
#: would cycle).
_SERVER, _WORKER, _COMMUNICATOR, _CONTROLLER = (
    "server", "worker", "communicator", "controller")


class _ServedTable:
    """Registry entry: a worker-table handle plus the serving-side
    per-table state — the index lock (one whole-table snapshot fetch
    at a time, and one versioned read, which counts hits around its
    Get), the batched scatter reader + hot-response cache
    (serving/batch.py), and the
    lazily refreshed nearest-neighbor index (brute snapshot + the
    optional IVF structure over it, serving/ann.py)."""

    __slots__ = ("name", "table", "vocab", "words", "lock",
                 "index_version", "index_generation", "index_values",
                 "index_norms", "ivf", "reader", "hot")

    def __init__(self, name: str, table, vocab: Optional[Dict[str, int]]):
        self.name = name
        self.table = table
        self.vocab = dict(vocab) if vocab else None
        self.words: Optional[List[Optional[str]]] = None
        if self.vocab:
            self.words = [None] * int(table.num_row)
            for word, row in self.vocab.items():
                if 0 <= int(row) < len(self.words):
                    self.words[int(row)] = word
        self.lock = threading.Lock()
        self.index_version = -1
        self.index_generation = -1
        self.index_values: Optional[np.ndarray] = None
        self.index_norms: Optional[np.ndarray] = None
        self.ivf: Optional[IVFIndex] = None
        self.reader: Optional[BatchedTableReader] = None
        self.hot: Optional[HotRowCache] = None


class ServingFrontend(HttpServer):
    def __init__(self, zoo, port: Optional[int] = None,
                 host: str = "0.0.0.0"):
        self._zoo = zoo
        self._tables: Dict[str, _ServedTable] = {}
        self._tables_lock = threading.Lock()
        self._max_rows = int(get_flag("serving_max_rows", 4096))
        self._scatter = bool(get_flag("serving_scatter", True))
        self._ann_nlist = int(get_flag("ann_nlist", 0))
        self._ann_nprobe = int(get_flag("ann_nprobe", 8))
        self.admission = AdmissionController(
            depth_of=self._mailbox_depth)
        super().__init__(
            int(get_flag("serving_port", 0)) if port is None else port,
            self._resolve_path, host=host, name="serving")
        # Fleet-pressure reporting (docs/SERVING.md fleet section):
        # ship this frontend's admission stats to the controller on a
        # cadence; the reply carries the fleet aggregate /v1/status
        # exposes for external load balancers.
        self._fleet_stop = threading.Event()
        self._fleet_thread: Optional[threading.Thread] = None
        interval = float(get_flag("serving_fleet_interval_s", 2.0))
        if interval > 0:
            self._fleet_thread = thread_roles.spawn(
                thread_roles.BACKGROUND, target=self._fleet_main,
                args=(interval,),
                name=f"mv-serving-fleet-{self.port}")

    # -- registry --
    def register_table(self, name: str, table,
                       vocab: Optional[Dict[str, int]] = None) -> None:
        """Expose a worker table under ``/v1/tables/<name>``. ``table``
        must speak the serving read contract (``read_rows_versioned``;
        dense matrix worker tables do). ``vocab`` (word -> row id)
        additionally enables word lookups on the neighbors endpoint."""
        if not hasattr(table, "read_rows_versioned"):
            raise ValueError(
                f"table {name!r} ({type(table).__name__}) does not "
                f"support serving reads (read_rows_versioned) — only "
                f"dense matrix worker tables serve (docs/SERVING.md)")
        entry = _ServedTable(name, table, vocab)
        if self._scatter and hasattr(table, "read_rows_scatter") \
                and not getattr(table, "is_sparse", False):
            entry.reader = BatchedTableReader(
                name, table, lambda t=table: self._bound_of_table(t))
            if int(get_flag("serving_hot_rows", 4096)) > 0 \
                    and hasattr(table, "cache_generation"):
                entry.hot = HotRowCache(
                    table, lambda t=table: self._bound_of_table(t))
        with self._tables_lock:
            self._tables[name] = entry
        log.info("serving: table %r registered (%d x %d, scatter=%s, "
                 "hot_cache=%s)", name, table.num_row, table.num_col,
                 entry.reader is not None, entry.hot is not None)

    # -- pressure probe (admission's depth gate) --
    def _mailbox_depth(self) -> int:
        depth = 0
        for name in (_SERVER, _WORKER):
            actor = self._zoo._actors.get(name)
            if actor is not None:
                depth = max(depth, actor.mailbox.size())
        return depth

    def _mailbox_report(self) -> dict:
        # The communicator is registered in the zoo but owns no mailbox
        # (it routes inline on caller threads; runtime/communicator.py),
        # so only mailbox-bearing registrants report.
        report = {}
        for name in (_SERVER, _WORKER, _COMMUNICATOR):
            actor = self._zoo._actors.get(name)
            mailbox = getattr(actor, "mailbox", None)
            if mailbox is not None:
                report[name] = {
                    "depth": mailbox.size(),
                    "high_watermark": mailbox.depth_high_watermark}
        return report

    # -- routing --
    def _resolve_path(self, path: str):
        if path == "/v1/status":
            return self._status
        if path == "/v1/tables":
            return self._list_tables
        parts = [p for p in path.split("/") if p]
        if len(parts) == 4 and parts[0] == "v1" \
                and parts[1] == "tables":
            name, endpoint = parts[2], parts[3]
            if endpoint == "rows":
                return lambda query: self._rows(name, query)
            if endpoint == "neighbors":
                return lambda query: self._neighbors(name, query)
        return None

    def describe(self) -> str:
        return ("/v1/status, /v1/tables, /v1/tables/<name>/rows, "
                "/v1/tables/<name>/neighbors")

    def _entry(self, name: str) -> _ServedTable:
        with self._tables_lock:
            entry = self._tables.get(name)
        if entry is None:
            with self._tables_lock:
                known = sorted(self._tables)
            raise HttpError(404, f"no table named {name!r} "
                                 f"(registered: {known})")
        return entry

    def _admit(self, endpoint: str) -> None:
        """Admission gate -> HTTP: a shed becomes 429/503 with the
        integer-seconds Retry-After header (HTTP grammar) and the
        precise float in the body."""
        try:
            self.admission.admit(endpoint)
        except ShedError as exc:
            raise HttpError(
                exc.status, str(exc),
                headers={"Retry-After": str(
                    max(int(math.ceil(exc.retry_after_s)), 1))},
                extra={"retry_after_s": exc.retry_after_s,
                       "shed": True}) from exc

    # -- endpoints --
    def _status(self, query) -> Response:
        with self._tables_lock:
            tables = {name: {"num_row": int(e.table.num_row),
                             "num_col": int(e.table.num_col),
                             "vocab": e.vocab is not None}
                      for name, e in self._tables.items()}
        # Rank identity + the controller-aggregated fleet view: behind
        # a load balancer every frontend answers /v1/status, and
        # without these fields the ranks are indistinguishable and
        # only LOCAL pressure is visible (docs/SERVING.md fleet
        # section). fleet is None until the first report round trips
        # (or with -serving_fleet_interval_s=0).
        fleet = getattr(self._zoo, "serving_fleet", None)
        return json_response({
            "rank": int(self._zoo.rank),
            "tables": tables,
            "admission": self.admission.stats(),
            "mailboxes": self._mailbox_report(),
            "fleet": fleet() if callable(fleet) else None})

    def _list_tables(self, query) -> Response:
        with self._tables_lock:
            names = sorted(self._tables)
        return json_response({"tables": names})

    def _parse_ids(self, entry: _ServedTable, query) -> np.ndarray:
        raw = query.get("ids")
        if not raw:
            raise HttpError(400, "missing ids= (comma-separated row "
                                 "ids)")
        try:
            ids = np.asarray([int(v) for v in raw.split(",") if v],
                             dtype=np.int32)
        except ValueError:
            raise HttpError(400, f"unparseable ids {raw!r}") from None
        if ids.size == 0:
            raise HttpError(400, "empty ids list")
        if ids.size > self._max_rows:
            raise HttpError(400, f"{ids.size} ids exceeds the "
                                 f"per-request cap "
                                 f"(-serving_max_rows="
                                 f"{self._max_rows})")
        if ids.min() < 0 or ids.max() >= entry.table.num_row:
            raise HttpError(400, f"row ids out of range [0, "
                                 f"{entry.table.num_row})")
        return ids

    def _rows(self, name: str, query) -> Response:
        entry = self._entry(name)
        ids = self._parse_ids(entry, query)
        self._admit("rows")
        t0 = time.perf_counter()
        try:
            # Hot-response cache first: the Zipf head serves straight
            # from rendered rows — no table call, no device, not even
            # the ndarray->list prep (serving/batch.py HotRowCache;
            # freshness = staleness bound + data generation).
            if entry.hot is not None:
                served = entry.hot.lookup(ids)
                if served is not None:
                    rendered, meta = served
                    count_event(CACHE_HIT)
                    return self._rows_response(
                        name, ids, rendered, meta, t0,
                        response_cache="hit")
            if entry.reader is not None:
                try:
                    values, meta, detail = entry.reader.read(ids)
                except UpstreamReadError as exc:
                    # Row-scoped upstream failure (dead shard owner /
                    # timeout): typed retryable rejection naming
                    # exactly the affected rows — rows on healthy
                    # shards in OTHER requests of the same batch were
                    # served normally, and a wrong value is never
                    # substituted.
                    retry = self.admission.retry_after_s
                    if exc.retryable:
                        raise HttpError(
                            503, str(exc),
                            headers={"Retry-After": str(max(
                                int(math.ceil(retry)), 1))},
                            extra={"retry_after_s": retry,
                                   "failed_rows": exc.rows,
                                   "retryable": True}) from exc
                    raise HttpError(
                        500, str(exc),
                        extra={"failed_rows": exc.rows,
                               "retryable": False}) from exc
                if entry.hot is not None:
                    entry.hot.store(detail)
                rendered = np.asarray(values).tolist()
            else:
                # -serving_scatter=false escape hatch: the serialized
                # PR-10 path, one read a table at a time.
                with entry.lock:
                    values, meta = entry.table.read_rows_versioned(ids)
                rendered = np.asarray(values).tolist()
            return self._rows_response(name, ids, rendered, meta, t0)
        finally:
            self.admission.release("rows")

    def _rows_response(self, name: str, ids: np.ndarray,
                       rendered: List, meta: dict, t0: float,
                       response_cache: str = "miss") -> Response:
        samples(LATENCY_MS).add((time.perf_counter() - t0) * 1e3)
        count_event(REQUESTS)
        return json_response(
            {"table": name, "ids": ids.tolist(), "rows": rendered,
             "response_cache": response_cache, **meta},
            headers=self._meta_headers(meta))

    @staticmethod
    def _meta_headers(meta: dict) -> Dict[str, str]:
        return {"X-MV-Version": str(meta["served_version"]),
                "X-MV-Latest-Version": str(meta["latest_version"]),
                "X-MV-Staleness-Bound": str(meta["staleness_bound"]),
                "X-MV-Cache":
                    "hit" if meta.get("cache_hit") else "miss"}

    # -- nearest neighbors (the word2vec inference demo) --
    def _neighbors(self, name: str, query) -> Response:
        entry = self._entry(name)
        try:
            k = int(query.get("k", "8"))
        except ValueError:
            raise HttpError(400, f"unparseable k {query.get('k')!r}") \
                from None
        k = min(max(k, 1), MAX_NEIGHBORS)
        word = query.get("word")
        if word is not None:
            if not entry.vocab:
                raise HttpError(400, f"table {name!r} has no vocab — "
                                     f"query by id= instead")
            row = entry.vocab.get(word)
            if row is None or not 0 <= int(row) < entry.table.num_row:
                raise HttpError(404, f"unknown word {word!r}")
            row = int(row)
        else:
            raw = query.get("id")
            if raw is None:
                raise HttpError(400, "need word= or id=")
            try:
                row = int(raw)
            except ValueError:
                raise HttpError(400, f"unparseable id {raw!r}") \
                    from None
            if not 0 <= row < entry.table.num_row:
                raise HttpError(400, f"row id {row} out of range "
                                     f"[0, {entry.table.num_row})")
        brute = query.get("brute") == "1"
        try:
            nprobe = int(query.get("nprobe", self._ann_nprobe))
        except ValueError:
            raise HttpError(400, f"unparseable nprobe "
                                 f"{query.get('nprobe')!r}") from None
        self._admit("neighbors")
        t0 = time.perf_counter()
        try:
            with entry.lock:
                refreshed = self._refresh_index(entry)
                values = entry.index_values
                norms = entry.index_norms
                index_version = entry.index_version
                ivf = entry.ivf
            # Scoring stays INSIDE the admission bracket: the scan
            # (IVF probe or the O(rows x cols) brute matmul) + top-k
            # is this endpoint's dominant cost, and releasing before
            # it would let an unbounded number of scoring threads run
            # concurrently — exactly the accepted-p99 convoy the
            # in-flight cap exists to prevent.
            q = values[row]
            if ivf is not None and not brute:
                # Probe-only timing: t0 would fold in the lock wait
                # and any index REBUILD (a whole-table fetch +
                # k-means), burying probe-latency regressions.
                t_probe = time.perf_counter()
                top_ids, top_scores, scanned = ivf.search(
                    q, k, nprobe, exclude=row)
                samples(ANN_PROBE_MS).add(
                    (time.perf_counter() - t_probe) * 1e3)
                index_kind = {"kind": "ivf", "nlist": ivf.nlist,
                              "nprobe": min(max(nprobe, 1), ivf.nlist),
                              "candidates": scanned}
            else:
                qn = float(np.linalg.norm(q))
                scores = (values @ q) / (norms * max(qn, 1e-12))
                scores[row] = -np.inf  # not its own neighbor
                top = np.argpartition(-scores,
                                      min(k, scores.size - 1))[:k]
                top_ids = top[np.argsort(-scores[top])]
                top_scores = scores[top_ids]
                index_kind = {"kind": "brute",
                              "candidates": int(scores.size)}
            neighbors = []
            for i, s in zip(top_ids, top_scores):
                item = {"id": int(i), "score": round(float(s), 6)}
                if entry.words is not None \
                        and entry.words[int(i)] is not None:
                    item["word"] = entry.words[int(i)]
                neighbors.append(item)
        finally:
            self.admission.release("neighbors")
        samples(LATENCY_MS).add((time.perf_counter() - t0) * 1e3)
        count_event(REQUESTS)
        latest = max(entry.table.observed_versions().values(),
                     default=-1)
        bound = self._bound_of(entry)
        meta = {"served_version": int(index_version),
                "latest_version": int(latest),
                "staleness_bound": int(bound),
                "cache_hit": not refreshed}
        return json_response(
            {"table": name,
             "query": {"id": int(row),
                       **({"word": word} if word is not None else {})},
             "k": k, "neighbors": neighbors, "index": index_kind,
             "index_refreshed": bool(refreshed), **meta},
            headers=self._meta_headers(meta))

    @staticmethod
    def _bound_of_table(table) -> int:
        cache = getattr(table, "_row_cache", None)
        return int(cache.bound) if cache is not None else 0

    @classmethod
    def _bound_of(cls, entry: _ServedTable) -> int:
        return cls._bound_of_table(entry.table)

    @staticmethod
    def _generation_of(entry: _ServedTable) -> int:
        gen = getattr(entry.table, "cache_generation", None)
        return int(gen()) if callable(gen) else 0

    def _refresh_index(self, entry: _ServedTable) -> bool:
        """Refresh the neighbor index when it has aged past the
        staleness bound — the SAME freshness rule the row cache
        applies, lifted to the whole-table snapshot: an index built
        when the newest observed shard version was ``v`` serves while
        ``latest - v <= bound`` — OR when the table's data generation
        changed (elastic reshard / server rejoin). Version staleness
        alone misses the latter: a restored or remapped shard's
        counter can restart BELOW the index anchor, so ``latest -
        index_version`` stays negative forever while the underlying
        rows change arbitrarily. Caller holds ``entry.lock``."""
        latest = max(entry.table.observed_versions().values(),
                     default=-1)
        generation = self._generation_of(entry)
        if entry.index_values is not None \
                and generation == entry.index_generation \
                and latest - entry.index_version <= \
                self._bound_of(entry):
            return False
        # Anchor to the versions observed BEFORE the fetch (the
        # read_rows_versioned rule): the get returns data at least
        # this fresh, while anchoring AFTER it would credit the index
        # with add-acks that landed mid-fetch — under a concurrent
        # trainer the index would then serve past the bound
        # undetected and served_version would overstate the snapshot.
        # The generation is pre-anchored for the same reason: a
        # reshard landing mid-fetch must invalidate THIS build.
        entry.index_version = latest
        entry.index_generation = generation
        values = np.array(self._fetch_all(entry), copy=True)
        entry.index_values = values
        norms = np.linalg.norm(values, axis=1)
        entry.index_norms = np.maximum(norms, 1e-12)
        entry.ivf = None
        if self._ann_nlist > 0:
            t0 = time.perf_counter()
            entry.ivf = IVFIndex(values, entry.index_norms,
                                 self._ann_nlist)
            log.debug("serving: IVF index for %r rebuilt (%d lists, "
                      "%.1f ms)", entry.name, entry.ivf.nlist,
                      (time.perf_counter() - t0) * 1e3)
        return True

    @staticmethod
    def _fetch_all(entry: _ServedTable) -> np.ndarray:
        return entry.table.get()

    # -- fleet-pressure reporting (docs/SERVING.md fleet section) --
    def _fleet_main(self, interval: float) -> None:
        """Reporter thread: every ``interval`` ship this frontend's
        admission pressure to the controller (Control_Serving_Report)
        and let the reply refresh the zoo's fleet-aggregate view.
        Frames ride ``net.send_async`` — never the communicator
        mailbox, whose dispatch thread can park toward a dead peer
        (the PR-6 liveness-frame discipline)."""
        while not self._fleet_stop.wait(timeout=interval):
            try:
                self._send_fleet_report()
            except Exception as exc:  # noqa: BLE001 - reporting is
                # best-effort; a hiccup must not kill the thread
                log.debug("serving: fleet report failed: %s", exc)

    def _send_fleet_report(self) -> None:
        from ..core.blob import Blob
        from ..core.message import Message, MsgType
        from ..runtime.zoo import CONTROLLER_RANK
        stats = self.admission.stats()
        msg = Message(src=self._zoo.rank, dst=CONTROLLER_RANK,
                      msg_type=MsgType.Control_Serving_Report)
        msg.push(Blob(np.asarray(
            [self._zoo.rank, stats["admitted"], stats["shed"],
             sum(stats["inflight"].values())], dtype=np.int64)))
        if self._zoo.rank == CONTROLLER_RANK:
            controller = self._zoo._actors.get(_CONTROLLER)
            if controller is not None:
                controller.receive(msg)
        else:
            self._zoo.net.send_async(msg)

    # -- lifecycle --
    def stop(self) -> None:
        """Graceful drain, then close: new requests reject with 503
        immediately; in-flight ones get up to ``-serving_drain_s``."""
        self._fleet_stop.set()
        if self._fleet_thread is not None:
            self._fleet_thread.join(timeout=5)
            self._fleet_thread = None
        drained = self.admission.begin_drain()
        if not drained:
            log.error("serving: drain timed out with requests still "
                      "in flight — closing anyway (%s)",
                      self.admission.stats()["inflight"])
        # Batcher threads stop AFTER the drain: in-flight requests may
        # still be parked on a batch that must execute.
        with self._tables_lock:
            entries = list(self._tables.values())
        for entry in entries:
            if entry.reader is not None:
                entry.reader.stop()
        super().stop()
