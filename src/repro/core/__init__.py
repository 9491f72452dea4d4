"""The paper's primary contribution: hybrid (topology+data-driven) worklist
scheduling with a persistent worklist — the IPGC steps, the worklist and
its capacity ladder, the result type and the verifier. Runs go through
``repro.exec.Session.run(ExecutionSpec(...), g)``."""
from repro.core.result import ColoringResult  # noqa: F401
from repro.core.baselines import jpl_color  # noqa: F401
from repro.core.worklist import Worklist, full_worklist, bucket_capacities  # noqa: F401
from repro.core.verify import InvalidColoringError, verify_coloring  # noqa: F401
from repro.core import ipgc  # noqa: F401
