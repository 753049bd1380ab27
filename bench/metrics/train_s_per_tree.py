"""Seconds of training per tree grown: the program's own
``TaskResult.train_seconds`` summed over the configurations the window
scored, over the trees they grew (``TaskResult.trees``: rounds trained
times the outputs per row for GBDT, K trees a round for K classes). It says
whether K trees grown in one launch cost less than K separate trees.
Nothing is read where the program does not count trees."""


def read(run):
    done = run.scored()
    trees = sum(int(getattr(r, "trees", 0) or 0) for r in done)
    if not done or trees <= 0:
        return None
    return sum(r.train_seconds for r in done) / trees
