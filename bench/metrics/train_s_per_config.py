"""Seconds of training per scored configuration: the program's own
``TaskResult.train_seconds`` summed over the configurations the window
scored, over their count (a fused batch reports each member's share)."""


def read(run):
    done = run.scored()
    if not done:
        return None
    return sum(r.train_seconds for r in done) / len(done)
