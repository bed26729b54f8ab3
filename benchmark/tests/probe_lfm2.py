"""``probe_bias.py`` for a cell of one sequence a batch: the same readings,
the routers' biases beside the reference's, and the ``half_batch`` fault as
half of the one sequence repeating the other half
(``keye_faults.half_repeated``; ``probe.half_batch`` halves the batch).

    python3 benchmark/tests/probe_lfm2.py --workload <cell> --seeds 1,2 \
        [--controls int8] [--faults half_batch] [--no-program]
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE]

import keye_faults  # noqa: E402
import probe_bias  # noqa: E402
import probe_tokens  # noqa: E402
from drivers import train_tokens  # noqa: E402

if __name__ == "__main__":
    probe_tokens.half_batch = keye_faults.half_repeated
    train_tokens.Driver = probe_bias.Driver
    probe_tokens.main()
