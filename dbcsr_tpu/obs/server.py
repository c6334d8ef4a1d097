"""Opt-in HTTP introspection endpoint for live long-running jobs.

A long-lived serve worker or a multihost perf run used to be a black box:
the only way to inspect it was to kill it and read JSONL off disk.
With ``DBCSR_TPU_OBS_PORT=<port>`` set (or `start()` called), every
engine process serves its live observability state over plain stdlib
``http.server`` — no dependencies, daemon thread, zero cost when off:

====================  ==================================================
route                 payload
====================  ==================================================
``/metrics``          Prometheus text exposition (`metrics.
                      prometheus_text()`) — scrapeable
``/healthz``          `health.verdict()` JSON; HTTP 200 for OK/
                      DEGRADED, 503 for CRITICAL (load-balancer
                      convention)
``/flight``           the flight-recorder ring (`flight.records()`)
``/events``           the event-bus ring; filters ``?product_id=…``,
                      ``?kind=…``, ``?limit=N``
``/serve/submit``     POST one serving-plane request (JSON body:
                      ``session``, ``a``/``b``/``c`` matrix names,
                      ``alpha``/``beta``/``op``/``priority``/
                      ``deadline_s``; optional ``wait`` +
                      ``timeout_s``); 503 when no engine runs, 429
                      with the structured rejection when shed
``/serve/status``     serving-plane snapshot (queue depth, in-flight,
                      coalescing/quota config); ``?request_id=…``
                      returns one request's ticket
``/serve/tenants``    per-tenant serving metrics: admitted/shed/
                      deadline-missed counters, queue load, rolling
                      p50/p95 latency
``/usage``            tenant cost-attribution rollup (`attribution.
                      usage()`): per-tenant device-seconds/flops/
                      bytes + saved credits, top consumers, grand
                      totals; ``?top=N``
``/timeseries``       telemetry history store (`obs.timeseries`):
                      ``?metric=&since=&until=&agg=&tier=`` + any
                      other param as a label matcher; no ``metric``
                      lists the known series
``/slo``              `obs.slo` burn-rate evaluation + the ``slo``
                      health component
``/cluster``          fleet federation: scrape the sibling processes'
                      endpoints (the multihost port-offset scheme, or
                      ``?ports=9100,9101`` / ``?n=4``) and merge them
                      into ONE exposition with per-process provenance
                      labels; ``?format=prom`` (default) or ``json``
``/``                 route index JSON
====================  ==================================================

**Multihost**: N processes sharing one env value must not fight over
one port — each binds ``base_port + process_index``.  When the index
is not yet knowable at activation (env activation runs before the
backend exists), the server starts on the base port best-effort and
`parallel.multihost.init_multihost` calls `rebind()` once the world
forms, restarting the listener on its offset port; a bind conflict at
activation simply defers the start to that rebind (same lazy-index
contract as `tracer._process_index`).

Loopback by default (``DBCSR_TPU_OBS_HOST``, default ``127.0.0.1``):
this is an introspection port, not a public API.
"""

from __future__ import annotations

import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from dbcsr_tpu.obs import tracer as _trace

_lock = threading.Lock()
_server: "ObsServer | None" = None
# /serve/stage's per-process materialization memo: (tenant, digest) ->
# matrix (the loadtest mat_cache contract — repeated digests reuse ONE
# object so the value-digest memo and product cache behave as live)
_stage_cache: dict = {}
# remembered when an early start() could not bind (index unknown and
# the base port was taken by another rank): rebind() retries with the
# resolved offset
_pending_base: int | None = None


class _Handler(BaseHTTPRequestHandler):
    server_version = "dbcsr-tpu-obs/1"

    def log_message(self, fmt, *args):  # silence per-request stderr spam
        pass

    def _send(self, body: str, content_type: str, code: int = 200) -> None:
        data = body.encode()
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def _send_json(self, obj, code: int = 200) -> None:
        self._send(json.dumps(obj, default=str), "application/json", code)

    def do_GET(self):  # noqa: N802 — http.server API
        try:
            url = urlparse(self.path)
            route = url.path.rstrip("/") or "/"
            if route == "/metrics":
                from dbcsr_tpu.obs import metrics

                self._send(metrics.prometheus_text(),
                           "text/plain; version=0.0.4")
            elif route == "/healthz":
                from dbcsr_tpu.obs import health

                v = health.verdict()
                self._send_json(
                    v, code=503 if v["status"] == health.CRITICAL else 200)
            elif route == "/flight":
                from dbcsr_tpu.obs import flight

                self._send_json(flight.records())
            elif route == "/events":
                from dbcsr_tpu.obs import events

                q = parse_qs(url.query)
                limit = None
                if "limit" in q:
                    try:
                        limit = int(q["limit"][0])
                    except ValueError:
                        pass
                self._send_json(events.records(
                    product_id=q.get("product_id", [None])[0],
                    kind=q.get("kind", [None])[0], limit=limit))
            elif route == "/timeseries":
                self._timeseries(parse_qs(url.query))
            elif route == "/rca":
                self._rca(parse_qs(url.query))
            elif route == "/profile/diff":
                self._profile_diff(parse_qs(url.query))
            elif route == "/slo":
                from dbcsr_tpu.obs import slo

                self._send_json({"objectives": slo.evaluate(),
                                 "component": slo.component()})
            elif route == "/cluster":
                self._cluster(parse_qs(url.query))
            elif route == "/serve/status":
                q = parse_qs(url.query)
                self._serve_status(q.get("request_id", [None])[0])
            elif route == "/serve/heartbeat":
                # fleet liveness probe: answers whether THIS process is
                # alive and routable — never 503s on a missing engine
                # (the router reads `engine`/`draining`, it does not
                # infer them from the status code)
                from dbcsr_tpu.serve import engine as _serve

                eng = _serve.current_engine()
                self._send_json({
                    "pid": os.getpid(),
                    "t_unix": time.time(),
                    "engine": eng is not None and eng.running(),
                    "draining": bool(eng.draining) if eng else False,
                    "queue_depth": eng.queue.depth() if eng else 0,
                })
            elif route == "/serve/checksum":
                self._serve_checksum(parse_qs(url.query))
            elif route == "/serve/cache":
                # fleet-shared product-cache tier: one entry by digest
                # handle (serve.product_cache.peer_lookup's wire call)
                from dbcsr_tpu.serve import product_cache as _pcache

                q = parse_qs(url.query)
                dig = q.get("digest", [None])[0]
                payload = _pcache.export_entry(dig) if dig else None
                if payload is None:
                    self._send_json({"found": False}, code=404)
                else:
                    self._send_json(dict(payload, found=True))
            elif route == "/tune/promotions":
                # fleet-shared tuning tier: this process's ORIGIN
                # promotions (never re-exported adoptions), filtered to
                # the caller's device kind (tune.store.peer_sync's
                # wire call)
                from dbcsr_tpu.tune import store as _tstore

                q = parse_qs(url.query)
                payload = _tstore.export_promotions(
                    kind=q.get("kind", [None])[0])
                if not payload.get("rows"):
                    self._send_json(dict(payload, found=False), code=404)
                else:
                    self._send_json(dict(payload, found=True))
            elif route == "/serve/tenants":
                eng = self._serve_engine()
                if eng is None:
                    return
                self._send_json(eng.tenants())
            elif route == "/usage":
                from dbcsr_tpu.obs import attribution

                q = parse_qs(url.query)
                try:
                    top = int(q.get("top", ["5"])[0])
                except ValueError:
                    top = 5
                self._send_json(attribution.usage(top=top))
            elif route == "/":
                self._send_json({
                    "routes": ["/metrics", "/healthz", "/flight",
                               "/events?product_id=&kind=&limit=",
                               "/timeseries?metric=&since=&agg=&tier=",
                               "/rca?limit=&ledger=",
                               "/profile/diff?a=&b=&top=",
                               "/slo",
                               "/cluster?format=prom|json&ports=&n=",
                               "/serve/submit (POST)",
                               "/serve/status?request_id=",
                               "/serve/tenants",
                               "/serve/heartbeat",
                               "/serve/checksum?session=&name=",
                               "/serve/cache?digest=",
                               "/tune/promotions?kind=",
                               "/serve/session/open (POST)",
                               "/serve/matrix (POST)",
                               "/serve/stage (POST)",
                               "/serve/drain (POST)",
                               "/serve/replay (POST)",
                               "/usage?top="],
                    "process_index": _server.process_index
                    if _server else None,
                })
            else:
                self._send_json({"error": f"no route {route}"}, code=404)
        except Exception as exc:  # introspection must never kill the job
            try:
                self._send_json(
                    {"error": f"{type(exc).__name__}: {exc}"}, code=500)
            except Exception:
                pass

    # -------------------------------------------------- telemetry history

    def _timeseries(self, q: dict) -> None:
        """``/timeseries``: query the live store.  Reserved params:
        ``metric``, ``since``, ``until``, ``agg``, ``tier``; every
        OTHER param is a label matcher (``?metric=…&driver=xla``).
        Without ``metric`` the known series are listed."""
        from dbcsr_tpu.obs import timeseries

        metric = q.get("metric", [None])[0]
        if not metric:
            self._send_json(timeseries.series_list())
            return
        reserved = ("metric", "since", "until", "agg", "tier", "format")
        labels = {k: v[0] for k, v in q.items() if k not in reserved}

        def num(name):
            raw = q.get(name, [None])[0]
            try:
                return float(raw) if raw not in (None, "") else None
            except ValueError:
                return None

        tier = q.get("tier", ["auto"])[0]
        if tier not in ("auto", "raw"):
            try:
                tier = float(tier)
            except ValueError:
                tier = "auto"
        self._send_json(timeseries.query(
            metric, labels=labels or None, since=num("since"),
            until=num("until"), agg=q.get("agg", [None])[0] or None,
            tier=tier))

    # --------------------------------------------- causal diagnosis plane

    def _rca(self, q: dict) -> None:
        """``/rca``: ranked causal reports + the change ledger + fired
        change-points, versioned by the obs schema (fleet merges key
        on it)."""
        from dbcsr_tpu import obs
        from dbcsr_tpu.obs import changepoint, rca

        limit = None
        try:
            raw = q.get("limit", [None])[0]
            limit = int(raw) if raw else None
        except ValueError:
            pass
        try:
            ledger_n = int(q.get("ledger", ["32"])[0])
        except ValueError:
            ledger_n = 32
        self._send_json({
            "schema": obs.OBS_SCHEMA_VERSION,
            "reports": rca.reports(limit=limit),
            "changepoints": changepoint.changepoints(limit=limit),
            "ledger": rca.ledger(limit=ledger_n),
        })

    def _profile_diff(self, q: dict) -> None:
        """``/profile/diff``: differential profile between two baseline
        snapshots.  ``a``/``b`` accept an epoch number, a negative ring
        index, or ``current``; defaults compare the previous sealed
        epoch against the newest profile state."""
        from dbcsr_tpu.obs import profiler

        def ref(name, default):
            raw = q.get(name, [None])[0]
            if raw in (None, ""):
                return default
            if raw == "current":
                return "current"
            try:
                return int(raw)
            except ValueError:
                return default

        try:
            top = int(q.get("top", ["8"])[0])
        except ValueError:
            top = 8
        a = ref("a", -2)
        b = ref("b", "current")
        d = profiler.diff(a, b, top=top)
        if b == "current" and not d.get("ok"):
            # a young process may have sealed nothing yet; fall back to
            # newest-sealed vs current before giving up
            d = profiler.diff(-1, "current", top=top)
        self._send_json(d)

    # --------------------------------------------------- fleet federation

    def _cluster(self, q: dict) -> None:
        """``/cluster``: scrape every sibling process's endpoint and
        merge into one fleet view with per-process provenance."""
        fmt = q.get("format", ["prom"])[0]
        ports = q.get("ports", [None])[0]
        n = q.get("n", [None])[0]
        peers = _cluster_peers(
            ports=[int(p) for p in ports.split(",") if p] if ports
            else None,
            n=int(n) if n else None)
        fleet = _fleet_mod()
        if fmt == "json":
            self._send_json(fleet.fleet_report(peers))
        else:
            self._send(fleet.merge_prometheus(peers),
                       "text/plain; version=0.0.4")

    # ------------------------------------------------------ serving plane

    def _serve_engine(self):
        """The live serving engine, or None (a 503 was sent).  The
        endpoint never CREATES an engine — serving is opt-in."""
        from dbcsr_tpu.serve import engine as _serve

        eng = _serve.current_engine()
        if eng is None:
            self._send_json(
                {"error": "serving plane not running "
                          "(dbcsr_tpu.serve.get_engine() starts it)"},
                code=503)
        return eng

    def _serve_status(self, request_id):
        eng = self._serve_engine()
        if eng is None:
            return
        if request_id:
            req = eng.get_request(request_id)
            if req is None:
                self._send_json(
                    {"error": f"unknown request {request_id}"}, code=404)
                return
            self._send_json(req.info())
            return
        self._send_json(eng.status())

    def do_POST(self):  # noqa: N802 — http.server API
        try:
            url = urlparse(self.path)
            route = url.path.rstrip("/")
            handlers = {
                "/serve/submit": self._serve_submit,
                "/serve/session/open": self._serve_session_open,
                "/serve/matrix": self._serve_matrix,
                "/serve/stage": self._serve_stage,
                "/serve/drain": self._serve_drain,
                "/serve/replay": self._serve_replay,
            }
            handler = handlers.get(route)
            if handler is None:
                self._send_json({"error": f"no POST route {route}"},
                                code=404)
                return
            length = int(self.headers.get("Content-Length") or 0)
            try:
                body = json.loads(self.rfile.read(length) or b"{}")
            except ValueError:
                self._send_json({"error": "bad JSON body"}, code=400)
                return
            handler(body)
        except Exception as exc:  # the serve paths must never kill the job
            try:
                self._send_json(
                    {"error": f"{type(exc).__name__}: {exc}"}, code=500)
            except Exception:
                pass

    def _resolve_session(self, body: dict):
        """The session named by ``body`` or None (a 404 was sent)."""
        from dbcsr_tpu.serve import session as _session

        sess = _session.get_session(str(body.get("session", "")))
        if sess is None:
            self._send_json(
                {"error": f"unknown session {body.get('session')!r}"},
                code=404)
        return sess

    def _serve_submit(self, body: dict) -> None:
        eng = self._serve_engine()
        if eng is None:
            return
        sess = self._resolve_session(body)
        if sess is None:
            return
        params = {k: body[k] for k in
                  ("a", "b", "c", "p", "alpha", "beta", "transa",
                   "transb", "filter_eps", "retain_sparsity", "steps",
                   "out")
                  if k in body}
        try:
            req = eng.submit(
                sess, op=str(body.get("op", "multiply")),
                priority=int(body.get("priority", 10)),
                deadline_s=body.get("deadline_s"),
                request_id=body.get("request_id"), **params)
        except KeyError as exc:  # unregistered matrix name
            self._send_json({"error": str(exc.args[0])}, code=404)
            return
        except ValueError as exc:  # unknown op
            self._send_json({"error": str(exc)}, code=400)
            return
        if body.get("wait"):
            req.wait(timeout=float(body.get("timeout_s", 30.0)))
        info = req.info()
        self._send_json(info, code=429 if req.state == "shed" else 200)

    def _serve_session_open(self, body: dict) -> None:
        """Open (or idempotently re-open) a session.  An explicit
        ``session_id`` is what lets the fleet router re-pin a dead
        worker's tenant sessions on a surviving peer under the SAME
        id, so journaled requests resolve; re-opening an id the same
        tenant already holds returns it (idempotent), another tenant's
        id is refused 409 — the session-name-collision guard."""
        eng = self._serve_engine()
        if eng is None:
            return
        tenant = str(body.get("tenant") or "")
        if not tenant:
            self._send_json({"error": "no tenant"}, code=400)
            return
        sid = body.get("session_id")
        if sid is not None:
            from dbcsr_tpu.serve import session as _session

            existing = _session.get_session(str(sid))
            if existing is not None:
                if existing.tenant != tenant:
                    self._send_json(
                        {"error": f"session id {sid!r} is held by "
                                  f"tenant {existing.tenant!r}"},
                        code=409)
                    return
                self._send_json({"session_id": existing.session_id,
                                 "tenant": existing.tenant,
                                 "existing": True})
                return
        sess = eng.open_session(tenant, name=sid)
        self._send_json({"session_id": sess.session_id,
                         "tenant": sess.tenant, "existing": False})

    def _serve_matrix(self, body: dict) -> None:
        """Create a matrix in a session by spec — ``random`` (the
        deterministic per-(session, name, seed) generator: two workers
        given the same spec materialize bitwise-equal values, the
        cross-worker failover re-pinning primitive) or ``create``
        (an empty result target)."""
        import numpy as np

        sess = self._resolve_session(body)
        if sess is None:
            return
        name = str(body.get("name") or "")
        row_blk = body.get("row_blk") or []
        col_blk = body.get("col_blk") or row_blk
        if not name or not row_blk:
            self._send_json({"error": "need name and row_blk"}, code=400)
            return
        dtype = np.dtype(str(body.get("dtype", "float64")))
        if str(body.get("kind", "random")) == "create":
            sess.create(name, row_blk, col_blk, dtype=dtype)
        else:
            sess.random(name, row_blk, col_blk, dtype=dtype,
                        occupation=float(body.get("occupation", 0.5)),
                        seed=int(body.get("seed", 0)))
        self._send_json({"ok": True, "session": sess.session_id,
                         "name": name})

    def _serve_stage(self, body: dict) -> None:
        """Stage one workload stream entry: materialize its operands
        into the session (digest-derived seeds — deterministic across
        workers) and return the submit kwargs.  The stage cache is
        per-process and memoizes per (tenant, digest) exactly like the
        loadtest harness's."""
        from dbcsr_tpu.serve import workload as _workload

        sess = self._resolve_session(body)
        if sess is None:
            return
        entry = body.get("entry")
        if not isinstance(entry, dict):
            self._send_json({"error": "no entry"}, code=400)
            return
        kwargs = _workload.stage_entry(sess, entry, _stage_cache)
        self._send_json({"ok": True, "session": sess.session_id,
                         "kwargs": kwargs})

    def _serve_drain(self, body: dict) -> None:
        eng = self._serve_engine()
        if eng is None:
            return
        self._send_json(eng.drain(
            timeout=float(body.get("timeout_s", 30.0)),
            journal_path=body.get("journal")))

    def _serve_replay(self, body: dict) -> None:
        """Replay a journal on THIS worker (the fleet failover target's
        side of the handoff): ``skip_ids`` are request ids the router's
        ledger knows completed elsewhere — tombstoned, never re-run."""
        eng = self._serve_engine()
        if eng is None:
            return
        tickets = eng.replay_journal(
            path=body.get("journal"),
            skip_ids=body.get("skip_ids") or ())
        self._send_json({"replayed": [t.request_id for t in tickets],
                         "count": len(tickets)})

    def _serve_checksum(self, q: dict) -> None:
        """``/serve/checksum?session=&name=``: the scalar checksum of
        one registered matrix (`ops.test_methods.checksum`) — what the
        fleet chaos case compares bitwise across workers."""
        from dbcsr_tpu.ops.test_methods import checksum
        from dbcsr_tpu.serve import session as _session

        sid = q.get("session", [None])[0]
        name = q.get("name", [None])[0]
        sess = _session.get_session(str(sid or ""))
        if sess is None:
            self._send_json({"error": f"unknown session {sid!r}"},
                            code=404)
            return
        try:
            m = sess.get(str(name or ""))
        except KeyError as exc:
            self._send_json({"error": str(exc.args[0])}, code=404)
            return
        self._send_json({"session": sess.session_id, "name": name,
                         "checksum": float(checksum(m))})


class ObsServer:
    """One listening introspection endpoint (daemon thread)."""

    def __init__(self, host: str, port: int, process_index: int):
        self.process_index = process_index
        self.httpd = ThreadingHTTPServer((host, port), _Handler)
        self.httpd.daemon_threads = True
        self.thread = threading.Thread(
            target=self.httpd.serve_forever, name="dbcsr-tpu-obs-server",
            daemon=True)
        self.thread.start()

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    @property
    def host(self) -> str:
        return self.httpd.server_address[0]

    def close(self) -> None:
        try:
            self.httpd.shutdown()
            self.httpd.server_close()
        except Exception:
            pass


def _host() -> str:
    return os.environ.get("DBCSR_TPU_OBS_HOST", "127.0.0.1")


def start(port: int | None = None) -> "ObsServer | None":
    """Start (or restart) the endpoint on ``base port +
    process_index``.  ``port=0`` binds an ephemeral port (tests).
    Returns the server, or None when the bind failed with the process
    index still unknown — `rebind` retries once `init_multihost`
    resolves it."""
    global _server, _pending_base
    if port is None:
        raw = os.environ.get("DBCSR_TPU_OBS_PORT")
        if not raw:
            raise ValueError(
                "no port: pass one or set DBCSR_TPU_OBS_PORT")
        port = int(raw)
    with _lock:
        if _server is not None:
            _server.close()
            _server = None
        idx = _trace._process_index() or 0
        bind_port = port + idx if port else 0
        try:
            _server = ObsServer(_host(), bind_port, idx)
            _pending_base = port if port else None
        except OSError:
            # base port taken (very likely a sibling rank on this host,
            # our own index not yet knowable): defer to rebind()
            _pending_base = port if port else None
            return None
        return _server


def stop() -> None:
    global _server, _pending_base
    with _lock:
        if _server is not None:
            _server.close()
            _server = None
        _pending_base = None


def running() -> bool:
    return _server is not None


def get() -> "ObsServer | None":
    return _server


def url() -> str | None:
    """The endpoint base URL, or None when not running."""
    s = _server
    return f"http://{s.host}:{s.port}" if s is not None else None


def rebind(process_index: int | None = None) -> None:
    """Settle the endpoint onto its ``base + process_index`` port once
    the world's index is known (called by `init_multihost`, mirroring
    `tracer.rebind`).  No-op when the endpoint was never requested or
    is already on its final port."""
    global _server
    base = _pending_base
    if base is None:
        return
    if process_index is None:
        process_index = _trace._process_index()
    if process_index is None:
        return
    idx = int(process_index)
    with _lock:
        if _server is not None and _server.process_index == idx \
                and _server.port == base + idx:
            return
        if _server is not None:
            _server.close()
            _server = None
        try:
            _server = ObsServer(_host(), base + idx, idx)
        except OSError:
            _server = None


# --------------------------------------------------- fleet federation
#
# The multihost port-offset scheme (each process serves base + index)
# already tells every process where its siblings listen; /cluster
# turns that into one fleet-wide view.  The scrape/relabel/merge core
# lives ONCE in tools/fleet.py (which must stay dbcsr_tpu-import-free
# for offline use on copied artifacts, so the server loads it by file
# path); only peer DISCOVERY lives here — it needs the server's bind
# state and the jax world.

_fleet = None


def _fleet_mod():
    """tools/fleet.py loaded by path (tools/ is not a package; the
    shared merge logic must not be duplicated here — it already
    drifted once)."""
    global _fleet
    if _fleet is None:
        import importlib.util

        root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        path = os.path.join(root, "tools", "fleet.py")
        spec = importlib.util.spec_from_file_location(
            "_dbcsr_tpu_fleet", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _fleet = mod
    return _fleet


def _cluster_peers(ports: list | None = None,
                   n: int | None = None) -> list:
    """[(index, url)] of the fleet's endpoints.  Explicit ``ports``
    win; else the remembered base port + the world's process count
    (falling back to probing up to 8 consecutive ports when no backend
    knows the count)."""
    host = _host()
    base = _pending_base
    if base is None and _server is not None:
        base = _server.port - _server.process_index
    if ports:
        # provenance must name the REAL process index: with the base
        # port known, index = port - base (so ?ports=9101 on a base
        # of 9100 labels process="1", and subsets stay truthful);
        # ports outside the offset scheme fall back to position
        out = []
        for i, p in enumerate(ports):
            idx = p - base if (base is not None
                               and 0 <= p - base < 4096) else i
            out.append((idx, f"http://{host}:{p}"))
        return out
    if base is None:
        return [(0, url())] if url() else []
    if n is None:
        import sys

        jax = sys.modules.get("jax")
        xb = sys.modules.get("jax._src.xla_bridge")
        if jax is not None and xb is not None \
                and getattr(xb, "_backends", None):
            try:
                n = int(jax.process_count())
            except Exception:
                n = None
    # no world evidence and no explicit count: the fleet is just this
    # process — fabricating sibling ports would report phantom peers
    # as down and page spuriously on a healthy single-process job
    count = n if n else 1
    return [(i, f"http://{host}:{base + i}") for i in range(count)]


# env activation: DBCSR_TPU_OBS_PORT set at import serves the endpoint
# with no code changes anywhere (mirrors DBCSR_TPU_TRACE); a bind
# conflict defers to init_multihost's rebind
if os.environ.get("DBCSR_TPU_OBS_PORT"):
    try:
        start()
    except (ValueError, OSError):
        pass
