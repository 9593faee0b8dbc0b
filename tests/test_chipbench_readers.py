"""The yardstick's own readers under tier-1: the FLOP table and the slice
arithmetic (test_arithmetic), the lap readers (test_laps, test_sampler_share),
the start-up readers (test_startup) and the trace reducer (test_trace) on
which every verdict in
PERF_LEDGER.jsonl rests. The cases live in chipbench/tests, which BENCHMARK.json
keeps out of a PR's reach and `pytest tests/` does not collect; the star
imports make each of them, parametrisation and all, a case of this file.
chipbench/tests/{test_cells,test_control}.py walk cells for minutes and stay
outside tier-1."""
from chipbench.tests.test_arithmetic import *  # noqa: F401,F403
from chipbench.tests.test_laps import *  # noqa: F401,F403
from chipbench.tests.test_sampler_share import *  # noqa: F401,F403
from chipbench.tests.test_trace import *  # noqa: F401,F403
# the readers of the program's start-up account (the seven `setup_*` metrics)
from chipbench.tests.test_startup import *  # noqa: F401,F403
# the LFM2 cell at its tiny sizes (four rehearsals, about a minute), its work
# functions and its readers
from chipbench.tests.test_cell_lfm2 import *  # noqa: F401,F403,E402
# the GigaChat3 cell likewise (four rehearsals), `mla_moe_work`'s counts and
# the four readers it brings
from chipbench.tests.test_cell_gigachat3 import *  # noqa: F401,F403,E402
# the SmallThinker cell likewise (five rehearsals: two page groups, a window
# of 8 and rings of 3 pages), `smallthinker_work`'s counts and the five
# readers it brings
from chipbench.tests.test_cell_smallthinker import *  # noqa: F401,F403,E402
