"""Model zoo: LLM families mirroring the reference's headline workloads
(BASELINE.json config ladder: GPT-2, Llama, Mixtral/MoE, ViT), SDAR
(sparse experts, generation by diffusion over blocks), Jamba
(state-space layers with attention among them) and Xing (latent
attention, sigmoid-routed experts beside a shared one, residual streams
mixed by hyper-connections)."""

from .gpt import GPT, GPTConfig  # noqa: F401
from .jamba import Jamba, JambaConfig  # noqa: F401
from .llama import Llama, LlamaConfig  # noqa: F401
from .mixtral import Mixtral, MixtralConfig  # noqa: F401
from .sdar import SDAR, SDARConfig  # noqa: F401
from .xing import Xing, XingConfig  # noqa: F401
from .ppocr import (DBNet, CRNNRecognizer, PPOCRSystem,  # noqa: F401
                    db_loss)
