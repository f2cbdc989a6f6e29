"""sweep_host_ms.sweep: host time of the program's spans ``sweep.init``
(the static-state draw), ``sweep.stage`` (plan and staging) and
``sweep.results`` (result assembly) per traced sweep (``bench.sweep``
span), in milliseconds: the host work around each sweep in which the
chip has nothing to run.  None where the trace holds none of them."""

SPANS = ("sweep.init", "sweep.stage", "sweep.results")


def read(ctx: dict):
    tr = ctx["trace"]
    host = tr.get("host", {})
    n = tr.get("span_counts", {}).get("bench.sweep", 0)
    if not n or not any(k in host for k in SPANS):
        return None
    return 1e3 * sum(host[k]["s"] for k in SPANS if k in host) / n
