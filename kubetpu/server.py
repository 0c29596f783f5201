"""Serving: /healthz, /metrics, /configz and the /debug observability
endpoints.

reference: cmd/kube-scheduler/app/server.go:167-199 (health + metrics
servers on the secure/insecure ports, configz registration) and
staging/src/k8s.io/component-base/configz.  The /debug family is the
TPU-native analog of the reference's pprof/debug endpoints
(DebuggingConfiguration): ``/debug/flightz`` dumps the flight recorder's
ring (``?format=chrome`` returns Perfetto-loadable Chrome trace-event
JSON), ``/debug/explain?pod=<name>[&namespace=<ns>]`` answers the per-pod
"why (un)scheduled" audit from the scheduler's DecisionLog (no pod
parameter lists the most recent decisions; ``?outcome=unschedulable``
filters), and ``/debug/journal`` reports the durable cycle journal's
status (utils/journal.py: records, bytes, drops, window span, linkage
hit-rates into the live flight/decision rings; ``armed: false`` when
KUBETPU_JOURNAL is unset).  Per-pod latency is upstream's histograms on
``/metrics``.
"""

from __future__ import annotations

import json
import threading
import urllib.parse
from dataclasses import asdict, is_dataclass
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from .utils import journal as ujournal
from .utils import trace as utrace


class SchedulerServer:
    def __init__(self, scheduler, host: str = "127.0.0.1", port: int = 10251):
        self.scheduler = scheduler
        self.host, self.port = host, port
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    def start(self) -> int:
        sched = self.scheduler

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _send(self, code: int, body: str,
                      ctype: str = "text/plain; charset=utf-8"):
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def _send_json(self, code: int, doc) -> None:
                self._send(code, json.dumps(doc, default=str, indent=2),
                           "application/json")

            def _flightz(self, query) -> None:
                fr = utrace.flight_recorder()
                if fr is None:
                    self._send_json(200, {
                        "armed": False,
                        "hint": "arm with KUBETPU_FLIGHT=1 or "
                                "kubetpu.utils.trace.arm_flight_recorder()"})
                    return
                fmt = (query.get("format") or ["json"])[0]
                if fmt in ("chrome", "perfetto"):
                    self._send_json(200, fr.to_chrome_trace())
                else:
                    self._send_json(200, fr.to_dict())

            def _explain(self, query) -> None:
                log = getattr(sched, "decisions", None)
                if log is None or not log.enabled:
                    self._send_json(200, {
                        "enabled": False,
                        "hint": "the decision audit is off "
                                "(KUBETPU_AUDIT=0)"})
                    return
                pod = (query.get("pod") or [None])[0]
                if not pod:
                    outcome = (query.get("outcome") or [None])[0]
                    try:
                        n = int((query.get("n") or ["50"])[0])
                    except ValueError:
                        self._send_json(400, {
                            "error": "n must be an integer"})
                        return
                    self._send_json(200, log.to_dict(n, outcome=outcome))
                    return
                ns = (query.get("namespace") or [None])[0]
                decision = log.get(pod, namespace=ns)
                if decision is None:
                    self._send_json(404, {
                        "error": f"no recorded decision for pod {pod!r}",
                        "hint": "the DecisionLog is bounded; the pod may "
                                "not have been attempted yet or its entry "
                                "was evicted"})
                    return
                self._send_json(200, decision.to_dict())

            def _journal(self, query) -> None:
                jr = ujournal.journal()
                if jr is None:
                    self._send_json(200, {
                        "armed": False,
                        "hint": "arm with KUBETPU_JOURNAL=<dir> or "
                                "kubetpu.utils.journal.arm_journal()"})
                    return
                fr = utrace.flight_recorder()
                flight_seqs = ({r.seq for r in fr.cycles()}
                               if fr is not None else None)
                log = getattr(sched, "decisions", None)
                decision_cycles = None
                if log is not None and log.enabled:
                    decision_cycles = {d.cycle
                                       for d in log.recent(log.capacity)}
                doc = jr.status(flight_seqs=flight_seqs,
                                decision_cycles=decision_cycles)
                doc["replay_hint"] = ("python -m tools.kubereplay "
                                      + jr.dir)
                self._send_json(200, doc)

            def do_GET(self):
                parsed = urllib.parse.urlparse(self.path)
                path = parsed.path
                query = urllib.parse.parse_qs(parsed.query)
                if path == "/healthz":
                    self._send(200, "ok")
                elif path == "/metrics":
                    # Prometheus text exposition format 0.0.4 content
                    # type either way (an empty registry is still a
                    # valid scrape)
                    body = ("" if sched.metrics is None
                            else sched.metrics.expose_text())
                    self._send(200, body, "text/plain; version=0.0.4")
                elif path == "/configz":
                    cfg = sched.config
                    doc = asdict(cfg) if is_dataclass(cfg) else vars(cfg)
                    self._send_json(200, doc)
                elif path == "/debug/flightz":
                    self._flightz(query)
                elif path == "/debug/explain":
                    self._explain(query)
                elif path == "/debug/journal":
                    self._journal(query)
                else:
                    self._send(404, "not found")

        self._httpd = ThreadingHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self.port

    def stop(self) -> None:
        if self._httpd:
            self._httpd.shutdown()
            self._httpd = None
            if self._thread is not None:
                self._thread.join(timeout=2.0)
                self._thread = None
