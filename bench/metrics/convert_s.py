"""Seconds the set-up spent converting the rows to every format the window
uses (the program's ``TaskResult.convert_seconds`` of the warm-up)."""


def read(run):
    return run.setup["convert_s"]
