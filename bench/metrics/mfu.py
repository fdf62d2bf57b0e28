"""Model FLOP utilisation of the traced window (see `bench/lib/work.mfu`),
read against `tpot_p50_ms`, beside the kernels' rooflines that move the
same metric."""
from bench.lib.work import mfu as read  # noqa: F401
