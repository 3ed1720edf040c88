"""Per-model declarative YAML config.

Parity target: the reference's ``BackendConfig``
(/root/reference/core/config/backend_config.go:28-246) — prediction defaults,
backend choice, prompt-template refs, grammar/function-calling config,
modality-specific sections, and feature flags — re-expressed for a TPU engine:
CUDA/GGUF-specific knobs (gpu_layers, mmap, ...) are replaced by sharding and
dtype/quantization knobs that map onto jax.sharding meshes and XLA.
"""

from __future__ import annotations

import enum
from typing import Any, Optional

from pydantic import BaseModel, ConfigDict, Field, model_validator


class Usecase(str, enum.Enum):
    """Capability flags a model can serve.

    Parity: BackendConfigUsecases bitmask
    (/root/reference/core/config/backend_config.go:"known_usecases").
    """

    CHAT = "chat"
    COMPLETION = "completion"
    EDIT = "edit"
    EMBEDDINGS = "embeddings"
    IMAGE = "image"
    TRANSCRIPT = "transcript"
    TTS = "tts"
    SOUND_GENERATION = "sound_generation"
    RERANK = "rerank"
    TOKENIZE = "tokenize"
    VISION = "vision"


class PredictionParams(BaseModel):
    """Sampling / prediction defaults merged with each request.

    Parity: PredictionOptions (/root/reference/core/schema/prediction.go) and
    the ``parameters:`` YAML section. All sampling runs on-device (see
    localai_tpu.engine.sampling); fields that only make sense for llama.cpp's
    CPU samplers (mirostat, tfz) are accepted and mapped or ignored with a
    warning rather than rejected, so reference YAML files keep loading.
    """

    model_config = ConfigDict(extra="allow", populate_by_name=True)

    temperature: Optional[float] = None
    top_p: Optional[float] = None
    top_k: Optional[int] = None
    min_p: Optional[float] = None
    max_tokens: Optional[int] = None
    frequency_penalty: Optional[float] = None
    presence_penalty: Optional[float] = None
    repeat_penalty: Optional[float] = None
    repeat_last_n: Optional[int] = None
    seed: Optional[int] = None
    echo: bool = False
    n: int = 1
    # Accepted-for-compat (llama.cpp-only samplers; engine maps or ignores):
    mirostat: Optional[int] = None
    mirostat_eta: Optional[float] = None
    mirostat_tau: Optional[float] = None
    typical_p: Optional[float] = None
    tfz: Optional[float] = None
    keep: Optional[int] = None

    def merged_with(self, overrides: dict[str, Any]) -> "PredictionParams":
        """Request-over-config merge (parity: updateRequestConfig,
        /root/reference/core/http/endpoints/openai/request.go:51+)."""
        data = self.model_dump(exclude_none=True)
        data.update({k: v for k, v in overrides.items() if v is not None})
        return PredictionParams(**data)


class TemplateConfig(BaseModel):
    """Prompt template references.

    Parity: TemplateConfig (/root/reference/core/config/backend_config.go:
    TemplateConfig struct). Templates here are Jinja2 (the HF ecosystem's
    native format) instead of Go text/template; ``use_tokenizer_template``
    selects the tokenizer's built-in chat template.
    """

    model_config = ConfigDict(extra="allow")

    chat: Optional[str] = None
    chat_message: Optional[str] = None
    completion: Optional[str] = None
    edit: Optional[str] = None
    functions: Optional[str] = None
    multimodal: Optional[str] = None
    use_tokenizer_template: bool = False
    # raw Jinja chat template (messages/add_generation_prompt), overriding
    # the tokenizer's own — filled by the family guesser
    # (config.guesser.guess_chat_defaults) for template-less configs
    chat_template: Optional[str] = None
    join_chat_messages_by_character: Optional[str] = None


class FunctionsConfig(BaseModel):
    """Function-calling / tool-use behavior.

    Parity: FunctionsConfig (/root/reference/pkg/functions/parse.go:15-50).
    On TPU, constrained decoding is token-level logit masking from a compiled
    FSM (localai_tpu.functions) rather than BNF text handed to a CPU sampler.
    """

    model_config = ConfigDict(extra="allow")

    disable_no_action: bool = False
    no_action_function_name: str = "answer"
    no_action_description_name: str = ""
    function_name_key: str = "name"
    function_arguments_key: str = "arguments"
    response_regex: list[str] = Field(default_factory=list)
    json_regex_match: list[str] = Field(default_factory=list)
    replace_function_results: list[dict[str, str]] = Field(default_factory=list)
    replace_llm_results: list[dict[str, str]] = Field(default_factory=list)
    capture_llm_results: list[str] = Field(default_factory=list)
    grammar: dict[str, Any] = Field(default_factory=dict)


class ShardingConfig(BaseModel):
    """How to lay the model over a jax.sharding.Mesh.

    This REPLACES the reference's gpu_layers/tensor_split/main_gpu/rpc_servers
    knobs (/root/reference/core/config/backend_config.go:116-117,151 and
    backend/cpp/llama/grpc-server.cpp:2233-2262): parallelism is compiled via
    pjit over ICI, not proxied over TCP. Axis sizes of 1 collapse; the product
    must divide the available device count (or equal it when data=0 → auto).
    """

    model_config = ConfigDict(extra="forbid")

    tensor_parallel_size: int = 1     # 'model' mesh axis (MXU-friendly TP)
    data_parallel_size: int = 0       # 0 = auto: fill remaining devices
    sequence_parallel_size: int = 1   # 'seq' axis: long-context ring attention
    expert_parallel_size: int = 1     # 'expert' axis for MoE layers
    pipeline_parallel_size: int = 1   # 'pipe' axis (layer stages)


class EngineConfig(BaseModel):
    """TPU serving-engine knobs.

    Replaces llama.cpp slot/cache flags (LLAMACPP_PARALLEL, n_ctx per slot —
    /root/reference/backend/cpp/llama/grpc-server.cpp:176,2223-2231) with
    static-shape equivalents: fixed slot count, paged KV in HBM, bucketed
    prefill lengths to bound XLA recompiles.
    """

    model_config = ConfigDict(extra="allow")

    max_slots: int = 8                # concurrent decode slots (continuous batching)
    page_size: int = 128              # KV page length (tokens); MXU/lane aligned
    prefill_buckets: list[int] = Field(
        default_factory=lambda: [128, 512, 2048, 8192]
    )
    dtype: str = "bfloat16"           # compute/weight dtype
    kv_dtype: str = "bfloat16"        # KV-cache dtype: bfloat16/float32,
                                      # scaled int8, or int4 (paged pools
                                      # only — nibble-packed along head_dim)
    quantization: Optional[str] = None  # "int8" | "int8_w8a8" | "int4"
    donate_kv: bool = True            # accepted for config compatibility; the
                                      # runner always donates the KV cache
    decode_steps_per_dispatch: int = 16  # tokens per dispatch (lax.scan):
                                      # one dispatch and one result fetch
                                      # per that many tokens; lower it for
                                      # tighter streaming cadence
    pipeline_depth: int = 2           # in-flight decode dispatches
    stream_latency_ms: float = 100.0  # SSE delivery-lag CEILING: with a
                                      # stream attached a dispatch holds the
                                      # fewest steps that hide the host's
                                      # work behind the device, and never so
                                      # many that steps×depth×step_time
                                      # passes this
    sp_prefill_threshold: int = 1024  # prompts at/above this many tokens
                                      # take the ring-attention prefill when
                                      # the mesh has a 'seq' axis
    attn_impl: str = "auto"           # auto | pallas | pallas_interpret | xla
    # Speculative decoding (parity: DraftModel/NDraft,
    # /root/reference/core/config/backend_config.go:143,
    # backend/backend.proto:210): a small same-vocab model proposes n_draft
    # tokens per window; the target verifies them in one batched forward.
    draft_model: Optional[str] = None
    n_draft: int = 4
    # Block-native speculation lane (localai_tpu.spec). None = auto: ON
    # for paged engines (LOCALAI_SPEC=0 force-disables, =1 has nothing
    # to add), OFF for contiguous engines unless draft_model is set.
    # spec_drafter picks the proposal source: "model" loads draft_model
    # co-located, "ngram" self-drafts via prompt lookup (no second model
    # — the single-model deployment default), "auto" = model when
    # draft_model is configured else ngram. spec_gamma is the window
    # size (draft tokens verified per dispatch; default n_draft, or
    # LOCALAI_SPEC_GAMMA).
    spec: Optional[bool] = None
    spec_drafter: str = "auto"
    spec_gamma: Optional[int] = None
    # Self-extend / group attention (parity: llama.cpp grp_attn_n/grp_attn_w,
    # grpc-server.cpp:210-211): grp_attn_n>1 serves up to
    # max_position_embeddings * grp_attn_n context via grouped positions —
    # see engine/selfextend.py for the TPU formulation.
    grp_attn_n: int = 1
    grp_attn_w: int = 512
    # Paged KV cache (vLLM-style block pool + chunked prefill;
    # engine/paged.py). None = auto: ON for single-device serving without
    # draft/self-extend/multi-host, OFF otherwise. kv_num_blocks sizes the
    # pool (None = the contiguous footprint: max_slots * ceil(ctx/block));
    # smaller pools overcommit HBM — admission then waits for free blocks.
    kv_paged: Optional[bool] = None
    kv_block_tokens: Optional[int] = None   # tokens per block (default 64
                                            # via LOCALAI_KV_BLOCK_TOKENS)
    kv_num_blocks: Optional[int] = None
    prefill_chunk: Optional[int] = None     # chunked-prefill dispatch size
                                            # (tokens; default 512)


class DiffusionConfig(BaseModel):
    """Image-generation section (parity: Diffusers struct,
    /root/reference/core/config/backend_config.go Diffusers section)."""

    model_config = ConfigDict(extra="allow")

    scheduler_type: Optional[str] = None
    cfg_scale: Optional[float] = None
    clip_skip: Optional[int] = None
    pipeline_type: Optional[str] = None
    enable_parameters: Optional[str] = None
    steps: Optional[int] = None
    # ControlNet model ref loaded next to the pipeline (backend.py:192-208)
    control_net: Optional[str] = None
    control_scale: float = 1.0


class TTSConfig(BaseModel):
    """TTS section (parity: TTSConfig,
    /root/reference/core/config/backend_config.go:19-26)."""

    model_config = ConfigDict(extra="allow")

    voice: Optional[str] = None
    audio_path: Optional[str] = None


class ModelConfig(BaseModel):
    """One model's declarative config (a YAML document in the models dir).

    Parity: BackendConfig (/root/reference/core/config/backend_config.go:28+).
    """

    model_config = ConfigDict(extra="allow", populate_by_name=True)

    name: str = ""
    backend: str = ""                       # worker type; "" = auto-select
    description: str = ""
    usage: str = ""
    model: str = ""                         # weights ref: hf repo / local path
    model_path: Optional[str] = None        # resolved absolute path (runtime)
    tokenizer: Optional[str] = None         # override tokenizer ref
    context_size: Optional[int] = None
    embeddings: bool = False
    seed: Optional[int] = None
    mmproj: Optional[str] = None            # vision tower ref (dir or debug:)
    image_token_id: Optional[int] = None    # placeholder id for image spans
                                            # (default: HF image_token_index
                                            # or 0; embeddings are injected
                                            # over these positions anyway)
    download_files: list[dict[str, Any]] = Field(default_factory=list)
    # LoRA adapters merged into base weights at load (parity:
    # backend_config.go:139-141; diffusers backend.py:300-314)
    lora_adapter: str = ""
    lora_base: str = ""                     # unused: merge needs no base copy
    lora_scale: float = 1.0
    # remote-API backends (backend: huggingface — pkg/langchain parity)
    api_token: str = ""
    api_base: str = ""

    parameters: PredictionParams = Field(default_factory=PredictionParams)
    template: TemplateConfig = Field(default_factory=TemplateConfig)
    function: FunctionsConfig = Field(default_factory=FunctionsConfig)
    sharding: ShardingConfig = Field(default_factory=ShardingConfig)
    engine: EngineConfig = Field(default_factory=EngineConfig)
    diffusers: DiffusionConfig = Field(default_factory=DiffusionConfig)
    tts: TTSConfig = Field(default_factory=TTSConfig)

    stopwords: list[str] = Field(default_factory=list)
    cutstrings: list[str] = Field(default_factory=list)
    extract_regex: list[str] = Field(default_factory=list)
    trimspace: list[str] = Field(default_factory=list)
    trimsuffix: list[str] = Field(default_factory=list)

    system_prompt: str = ""
    roles: dict[str, str] = Field(default_factory=dict)

    feature_flags: dict[str, bool] = Field(default_factory=dict)
    known_usecases: Optional[list[Usecase]] = None

    # Compat fields accepted from reference YAMLs and mapped:
    f16: Optional[bool] = None              # → engine.dtype bfloat16 (TPU norm)
    threads: Optional[int] = None           # ignored: XLA owns threading
    gpu_layers: Optional[int] = None        # ignored: no host/device layer split
    tensor_parallel_size: Optional[int] = None  # → sharding.tensor_parallel_size
    low_vram: Optional[bool] = None         # ignored
    mmap: Optional[bool] = None             # ignored
    prompt_cache_path: Optional[str] = None
    prompt_cache_all: bool = False
    prompt_cache_ro: bool = False
    grammar: str = ""                       # raw grammar text (GBNF-compatible)
    rope_scaling: Optional[str] = None      # linear|yarn → models.llama rope
    rope_freq_base: Optional[float] = None
    rope_freq_scale: Optional[float] = None

    @model_validator(mode="after")
    def _apply_compat(self) -> "ModelConfig":
        if self.tensor_parallel_size and self.sharding.tensor_parallel_size == 1:
            self.sharding.tensor_parallel_size = self.tensor_parallel_size
        if self.f16 is False:
            self.engine.dtype = "float32"
        return self

    def set_defaults(self, *, context_size: int = 4096, debug: bool = False) -> None:
        """Fill unset fields (parity: BackendConfig.SetDefaults,
        /root/reference/core/config/backend_config.go)."""
        p = self.parameters
        if p.temperature is None and p.mirostat in (None, 0):
            p.temperature = 0.9
        if p.top_p is None:
            p.top_p = 0.95
        if p.top_k is None:
            p.top_k = 40
        if p.max_tokens is None:
            p.max_tokens = 2048
        if self.context_size is None:
            self.context_size = context_size
        if not self.name and self.model:
            self.name = self.model

    def validate_config(self) -> bool:
        """Minimal sanity validation (parity: BackendConfig.Validate).
        Rejects '..' traversal segments in file refs; absolute paths are
        allowed (they are resolved against verify_path at use sites)."""
        if not self.name:
            return False
        return not any(
            ".." in f.split("/")
            for f in (self.model, self.backend, self.mmproj or "")
        )

    def has_usecase(self, uc: Usecase) -> bool:
        """Usecase gating (parity: HasUsecases/GuessUsecases,
        /root/reference/core/config/backend_config.go known_usecases)."""
        if self.known_usecases is not None:
            return uc in self.known_usecases
        return uc in self.guess_usecases()

    def guess_usecases(self) -> set[Usecase]:
        guessed: set[Usecase] = set()
        name = (self.backend or "").lower()
        if self.embeddings or "embed" in name:
            guessed.add(Usecase.EMBEDDINGS)
        if name in ("", "jax", "jax-llm", "transformers", "worker",
                    "huggingface", "langchain-huggingface", "mamba",
                    "rwkv"):
            guessed |= {
                Usecase.CHAT,
                Usecase.COMPLETION,
                Usecase.EDIT,
                Usecase.TOKENIZE,
            }
            if self.mmproj:
                guessed.add(Usecase.VISION)
            if self.embeddings:
                guessed.add(Usecase.EMBEDDINGS)
        if "diffus" in name or "image" in name:
            guessed.add(Usecase.IMAGE)
        if "whisper" in name:
            guessed.add(Usecase.TRANSCRIPT)
        if "tts" in name or name == "vits":
            guessed.add(Usecase.TTS)
        if "musicgen" in name or "sound" in name:
            guessed.add(Usecase.SOUND_GENERATION)
        if "rerank" in name:
            guessed.add(Usecase.RERANK)
        if self.embeddings:
            # embedding-capable models can score query/document pairs
            guessed.add(Usecase.RERANK)
        return guessed
