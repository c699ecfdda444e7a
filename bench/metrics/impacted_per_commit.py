"""Cache keys the gRW commit step invalidated per commit over the window:
the commits' ``impacted_keys`` over the commit steps."""


def read(run):
    n = run.out.commit_steps
    return run.out.commit_metrics.get("impacted_keys", 0) / n if n else None
