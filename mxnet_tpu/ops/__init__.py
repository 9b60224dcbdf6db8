"""Operator library: importing this package registers every op.

The registry (ops/registry.py) is the single source from which the
``mx.nd.*`` and ``mx.sym.*`` namespaces are generated, mirroring how the
reference generates Python functions from its C++ NNVM registry.
"""
from .registry import OpDef, register, get_op, list_ops, alias  # noqa: F401
from . import elemwise    # noqa: F401
from . import reduce      # noqa: F401
from . import tensor      # noqa: F401
from . import nn          # noqa: F401
from . import random_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import linalg      # noqa: F401
from . import rnn         # noqa: F401
from . import ctc         # noqa: F401
from . import contrib     # noqa: F401
from . import contrib_extra  # noqa: F401
from . import contrib_extra3  # noqa: F401
from . import spatial     # noqa: F401
from . import lm          # noqa: F401

from . import shape_infer as _shape_infer  # noqa: E402
_shape_infer.install()

# dynamic output counts
from .registry import get_op as _g  # noqa: E402
_g("topk").visible_outputs = lambda p: 2 if p.get("ret_typ") == "both" else 1
