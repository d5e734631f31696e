"""``python3 -m tcqbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>``"""

import time

T_START = time.time()

import sys  # noqa: E402

from tcqbench.harness import main  # noqa: E402

sys.exit(main(t_start=T_START))
