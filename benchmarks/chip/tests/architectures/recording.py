"""A tests-only architecture: every function of the contract is the dense
module's, and each call is counted in ``CALLS``.  A configuration that
names it runs exactly as a dense one, so a rehearsal through it shows which
pieces the harness takes from the configuration's module."""
from collections import Counter

from chipbench import BENCH_DIR
from chipbench.model import ARCH_CONTRACT, load_architecture_file

dense = load_architecture_file(BENCH_DIR / "architectures" / "dense.py")
CALLS: Counter = Counter()


def _recorded(name: str):
    fn = getattr(dense, name)

    def call(*args, **kwargs):
        CALLS[name] += 1
        return fn(*args, **kwargs)
    return call


for _name in ARCH_CONTRACT:
    globals()[_name] = _recorded(_name)
