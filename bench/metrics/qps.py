"""Queries answered in the window over the window's seconds. The window
runs from the first submit to the end of the batch that crosses
`--seconds`, so every batch counted is counted whole."""
UNIT = "queries/s"


def read(run):
    return run.window.served_in_window() / run.window.seconds
