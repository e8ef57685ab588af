"""Share of the traced stretch that the worker's spans cover
(``host_spans``) in which the device was idle under ``serve.execute`` (the
rest of the upload, the launch, the completion's notice, the clocks' skew)
or under no span of the program, in the cell judged on throughput."""
from benchmark.host_spans import idle_share_pct


def read(observed):
    return idle_share_pct(observed, None)
