"""Serve, ingress: what the HTTP proxy adds to a request of an otherwise
idle system, in milliseconds — the median of `http_probes` requests through
the per-node proxy (JSON in, JSON out, `serve/proxy.py` + `serve/api.py::
_gateway_server`) less the median of the same document through the handle,
sent in turn, one at a time, once a traced run's window has closed
(`loops/serve.py::proxy_probe`). The window's requests all go through the
handle (the proxy does not sustain the rate: PERF.md section 4)."""

import statistics


def read(run):
    took = (run.get("traced") or {}).get("proxy_probe_ms")
    if not took or not took["http"] or not took["handle"]:
        return None
    return statistics.median(took["http"]) - statistics.median(took["handle"])
