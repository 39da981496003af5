"""Model builders over the Program IR (lenet .. gpt: imported here, each a
`*_program` for pt.Executor) and, beside them, the SERVED models, which the
engine reaches through serving.model.ServingModel and which are imported only
when an engine over their config is built. Six models on five blocks, each a
LEAF that imports shared pieces and never another model: `gpt_decode` (the GPT
family), `moonlight` (Moonlight-16B-A3B and, by three fields of the one config,
Xing4.0-29B-A4B: latent attention, a residual mixer), `mellum`, `command_a`,
`sdar` (grouped-query attention over cache groups; SDAR generates by diffusion
over blocks). The shared pieces, imported by none of the lines below:
`_decoder` (norm, rotary positions, masked attention, head), `_experts` (the
routed + shared expert layer of every block but GPT's), `_grouped` (grouped
attention, full layers and window rings), and serving/pages.py (the arena's
readers and writers, the one rule for where a kernel may sit)."""

from . import lenet  # noqa: F401
from . import book  # noqa: F401
from . import resnet  # noqa: F401
from . import vgg  # noqa: F401
from . import deepfm  # noqa: F401
from . import transformer  # noqa: F401
from . import bert  # noqa: F401
from . import gpt  # noqa: F401
