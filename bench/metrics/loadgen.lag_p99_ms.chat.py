"""How late the open-loop player submitted requests: the 99th percentile of
(submit time - due time) over every request due in the window, in ms, on the
harness's clock. A starved player would otherwise read as a fast server.
Moves ``ttft_p50_s`` (a request is timed from when it was due)."""

from bench.loadgen import percentile


def read(ctx):
    lag = ctx.rec.get("lag")
    if not lag:
        return None
    return 1e3 * percentile(list(lag.values()), 99)
