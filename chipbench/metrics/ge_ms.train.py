"""Device milliseconds per train step in gradient estimation (the
program's ``ge`` phase, paper Table 4): every client's ± perturbed
forwards, their losses and α."""
from chipbench import phases


def read(m):
    return phases.ms_per_step(m, "ge")
