"""``probe_tokens.py`` for a cell whose router is balanced by a bias: the same
readings, and for every seed, to standard error, the routers' biases after
the first dispatch by the program (its layer state, read before the driver
frees it) beside the reference's (``follow``'s ``router_bias``): per expert
layer the number of entries that moved the other way in some step (they
differ by a whole step's rate or more; an odd number of them also shifts
every entry by the re-centring, 2 x rate / outputs each), and the largest
difference.

    python3 benchmark/tests/probe_bias.py --workload <cell> --seeds 1,2 ...
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE]

import numpy as np  # noqa: E402

import probe_tokens  # noqa: E402
from drivers import train_tokens  # noqa: E402

_program = {}


class Driver(train_tokens.Driver):
    def release(self):
        if self.net is not None:
            _program.clear()
            _program.update({
                str(i): np.asarray(st["router_bias"])
                for i, st in enumerate(self.net.state_list)
                if isinstance(st, dict) and "router_bias" in st})
        super().release()

    def reference(self, precision: str = "float32") -> dict:
        out = super().reference(precision)
        if precision == "float32" and _program and "router_bias" in out:
            for layer, want in sorted(out["router_bias"].items()):
                got, want = _program[layer], np.asarray(want, np.float32)
                rate = float(self.kwargs.get("load_balance_coeff", 0.001))
                off = np.abs(got - want) > 0.5 * rate
                self.tools.log(
                    f"router bias after {self.k} steps, layer {layer}: "
                    f"{int(off.sum())} of {off.size} entries differ by a "
                    f"step or more, the others by at most "
                    f"{np.abs(got - want)[~off].max(initial=0.0):.3g} "
                    f"(largest difference {np.abs(got - want).max():.4g}; "
                    f"program in [{got.min():.4g}, {got.max():.4g}], "
                    f"reference in [{want.min():.4g}, {want.max():.4g}])")
            _program.clear()      # the controls and faults that follow are
        return out                # reference against reference


if __name__ == "__main__":
    train_tokens.Driver = Driver
    probe_tokens.main()
