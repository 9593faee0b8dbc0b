"""Seconds the program's compile registry spent tracing, lowering and
compiling inside set-up (its goodput accountant's ``compile`` phase)."""


def read(facts):
    return facts.get("compile_s")
