"""Model zoo beyond vision: the flagship transformer family used by the
benchmarks (BASELINE.json configs #3-#5)."""
from .gpt import (  # noqa: F401
    GPTConfig, GPTModel, GPTForCausalLM, GPTPretrainingCriterion,
    StaticKVCache, gpt_configs)
from .nemotron_h import (NemotronHConfig, NemotronHModel,  # noqa: F401
                         NemotronHForCausalLM)
from .kimi_linear import (KimiLinearConfig, KimiLinearModel,  # noqa: F401
                          KimiLinearForCausalLM)
from .recurrent_cache import (HybridStateCache, KVRows,  # noqa: F401
                              MambaLayerView, MambaState,
                              RecurrentStateCache, RetentionLayerView)
from .brumby import (BrumbyConfig, BrumbyModel,  # noqa: F401
                     BrumbyForCausalLM)
from .granite_hybrid import (GraniteHybridConfig,  # noqa: F401
                             GraniteHybridModel, GraniteHybridForCausalLM)
