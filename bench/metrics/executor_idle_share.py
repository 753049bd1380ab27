"""Share of the executors' time in the window in which none of them trained,
scored or converted: 1 - sum(train + eval + convert seconds) / (span x
executors), in percent, over whole searches. What is left is profiling,
planning, dispatch, result handling and executors that wait their turn. A
reading below 0 means the program's seconds count some work twice."""


def read(run):
    span = run.span_s()
    done = run.done()
    if span <= 0 or not done:
        return None
    work = sum(r.train_seconds + r.eval_seconds + r.convert_seconds
               for r in done)
    return 100.0 * (1.0 - work / (span * run.n_executors))
