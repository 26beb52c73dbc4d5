"""Model zoo mirroring the reference's benchmark + book models
(reference: benchmark/fluid/models/{mnist,resnet,vgg,
stacked_dynamic_lstm,machine_translation}.py and
python/paddle/fluid/tests/book/), and three decoder blocks of 2025
sparse models (not in the reference): ``afmoe``, ``kimi_linear`` with
its linear-attention and latent-attention mixers, and ``deepseek_v3``
with latent attention's rotary form in every layer (``mla`` is the
mixer the last two share)."""

from . import afmoe  # noqa: F401
from . import bert  # noqa: F401
from . import deepfm  # noqa: F401
from . import deepseek_v3  # noqa: F401
from . import kimi_linear  # noqa: F401
from . import mla  # noqa: F401
from . import mnist  # noqa: F401
from . import recommender  # noqa: F401
from . import resnet  # noqa: F401
from . import se_resnext  # noqa: F401
from . import stacked_lstm  # noqa: F401
from . import transformer  # noqa: F401
from . import vgg  # noqa: F401
from . import word2vec  # noqa: F401
