"""`setup_s` less what the program's start-up account covers: backend
start, the harness's reference weights, request plan and warm-up; the part
of set-up that is the machine's and the benchmark's."""


def read(facts):
    from chipbench.lib import startup

    return startup.outside_program_s(facts)
