"""RPC front end (core/service.py): mean per request of the client's call
time (send to reply) less the server's ``server.rank`` span under it."""
from bench import spans as S


def read(run):
    kids = S.children(run.spans)
    gaps = [c.dur_us - k.dur_us for c in S.by_name(run.spans, "client.rank")
            for k in kids.get(c.span_id, ()) if k.name == "server.rank"]
    return sum(gaps) / len(gaps) / 1e3 if gaps else None
