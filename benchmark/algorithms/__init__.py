"""One module per algorithm a configuration can serve, found by the
configuration's ``algorithm.module``. Each holds the algorithm's plain
reference, the comparison of a served row with it, the lower-precision
control, and the least bytes its kernel must move:

    reference(vm, src, dst, alg)  -> the answer, from the folded graph
    control(vm, src, dst, alg)    -> a served-like answer, one precision down
    stated(vm, src, dst, alg)     -> the same in the stated precision
    compare(row, want, limits, alg) -> every number compared, and ``ok``
    least_bytes(columns, alg)     -> bytes of one dispatch over ``columns``,
                                     a list of (alive vertices, alive pairs)

``alg`` is the configuration's ``algorithm`` group. numpy only: nothing
of the program, and nothing the program has made.
"""

import importlib


def load(name: str):
    return importlib.import_module(f"benchmark.algorithms.{name}")
