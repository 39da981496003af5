"""Model builders over the Program IR (lenet .. gpt: imported here, each a
`*_program` for pt.Executor) and, beside them, the SERVING models, which the
engine reaches through serving.model.ServingModel and which are imported only
when an engine over their config is built: `gpt_decode` (the GPT family: the
KV-cache kernels behind `GPTConfig.serving_model()`) and `moonlight`
(Moonlight-16B-A3B, the DeepSeek-V3 block: latent attention over a latent page
arena, routed and shared experts; `MoonlightConfig.serving_model()`)."""

from . import lenet  # noqa: F401
from . import book  # noqa: F401
from . import resnet  # noqa: F401
from . import vgg  # noqa: F401
from . import deepfm  # noqa: F401
from . import transformer  # noqa: F401
from . import bert  # noqa: F401
from . import gpt  # noqa: F401
