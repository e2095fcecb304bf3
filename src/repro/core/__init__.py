# The paper's primary contribution: the OpenGCRAM memory compiler in JAX —
# device models, bitcells, macro composition, SPICE-style characterization
# (delay/power/retention), netlist+layout with DRC/LVS checks, artifact
# emission, and the heterogeneous-memory design-space exploration engine.
#
# The public entry point is the `repro.api` façade (Compiler / DesignTable /
# explore); the names below are the physics layer.
from repro.core.macro import MacroConfig  # noqa: F401
from repro.core.characterize import characterize_batch, characterize_config  # noqa: F401
from repro.core.retention import retention_time, decay_curve, retention_estimate  # noqa: F401
from repro.core.artifacts import generate_all  # noqa: F401
from repro.core import characterize, gainsight, retention  # noqa: F401,F811

# keep the submodules (not same-named functions) bound on the package
import sys as _sys
characterize = _sys.modules["repro.core.characterize"]
retention = _sys.modules["repro.core.retention"]
