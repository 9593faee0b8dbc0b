"""Runner of a serving cell whose closed loop holds more than a minute of
answers outstanding: ``serve.py``'s run, whole, with a longer drain.

``serve.py`` gives the client threads ``DRAIN_S`` = 60 s to end once the
load stops, then shuts the server down, and in a closed loop a request cut
off there counts as failed.  A loop of 512 clients on a model that answers
4.8 requests a second needs 85-110 s (PERF.md section 6, PR 43), so this
runner waits for up to ``DRAIN_S`` = 240 s and changes nothing else: the
wait ends as soon as the last client thread does, the window, the counters
and the comparison that decides `correct` are ``serve.run``'s, and the cells
that name ``chipbench.runners.serve`` keep its 60 s (a process runs one
cell; a test process that runs several finds the constant put back).
"""
from __future__ import annotations

from . import serve

DRAIN_S = 240.0         # the longest wait for the client threads to end


def run(cell, config, traffic, opts, t_process):
    short, serve.DRAIN_S = serve.DRAIN_S, DRAIN_S
    try:
        return serve.run(cell, config, traffic, opts, t_process)
    finally:
        serve.DRAIN_S = short
