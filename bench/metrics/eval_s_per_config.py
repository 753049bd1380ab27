"""Seconds of scoring per scored configuration: the program's own
``TaskResult.eval_seconds`` summed over the configurations the window
scored, over their count."""


def read(run):
    done = run.scored()
    if not done:
        return None
    return sum(r.eval_seconds for r in done) / len(done)
