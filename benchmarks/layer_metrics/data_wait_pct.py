"""Input pipeline: the share of the traced stretch that ``fit`` spent waiting
on the iterator. Source: the program's own ``dl4j_train_data_wait_seconds``
histogram (host clock), its sum over the traced stretch; monitoring is on in
the traced run only (``ParallelWrapper.fit`` records its wait there too,
since PR 26). A fit loop that has no such phase records nothing, and the
metric is left out."""


def read(ctx):
    wait = ctx["counters"].get("data_wait_s")
    if wait is None:
        return None
    return 100.0 * wait / ctx["counters"]["traced_host_s"]
