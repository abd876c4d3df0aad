"""Requests that had arrived and had not finished when the window closed:
waiting at the front door, waiting in the engine, or decoding. They are
neither attempted nor failed."""


def read(run):
    return float(run.window["backlog"])
