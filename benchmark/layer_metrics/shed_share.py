"""HTTP front door: 429 and 503 answers over requests sent, %, counted
by the client over the requests due inside the window."""


def read(r):
    if r.get("mode") != "open":
        return None
    t0, t1 = r["t0"], r["t1"]
    sent = [x for x in r["records"]
            if x["due"] is not None and t0 <= x["due"] < t1
            and x["status"] is not None]
    if not sent:
        return None
    return 100.0 * sum(x["status"] in (429, 503) for x in sent) / len(sent)
