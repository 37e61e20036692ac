"""The serve window's tails as per-layer records, for cells that are not
judged by them: above the knee the queue grows all through the run, so the
tails and the share left unserved swing with the smallest change."""


def itl_p95_ms(ctx):
    return ctx["end_to_end"].get("itl_p95_ms")


def ttft_p95_ms(ctx):
    return ctx["end_to_end"].get("ttft_p95_ms")


def backlog_unserved_pct(ctx):
    host = ctx["host"]
    if host.get("unserved") is None or not host.get("counted"):
        return None
    return 100.0 * host["unserved"] / host["counted"]
