"""Batch LLM inference over ray_tpu.data (the reference's ray.data.llm).

Counterpart of /root/reference/python/ray/llm/_internal/batch/processor/
(vllm_engine_proc.py + stages/): build_llm_processor returns a
Dataset -> Dataset callable whose stages are map_batches ops — tokenize →
engine generate (actor pool, one engine per actor) → detokenize.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

from ray_tpu.llm.engine import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.llm.tokenizer import get_tokenizer


@dataclass
class ProcessorConfig:
    """Reference: batch/processor/__init__.py ProcessorConfig lineage."""

    # () -> (params, the model's configuration): whatever the programs of
    # llm/model.py serve, a LlamaConfig or an SDARMoEConfig (their docstring
    # says what they read of it)
    model_loader: Callable = None
    tokenizer: Optional[str] = None
    engine_config: EngineConfig = field(default_factory=EngineConfig)
    concurrency: int = 1  # engine actors
    batch_size: int = 16
    sampling: Dict[str, Any] = field(default_factory=dict)
    num_tpus: Optional[float] = None
    # wrap each prompt in the tokenizer's chat template (reference:
    # batch/stages/chat_template_stage.py)
    apply_chat_template: bool = False


class _EngineUDF:
    """Actor-pool UDF hosting one engine (reference:
    vllm_engine_proc.py engine stage)."""

    def __init__(self, config: ProcessorConfig):
        params, model_cfg = config.model_loader()
        self._tok = get_tokenizer(config.tokenizer)
        self._engine = LLMEngine(params, model_cfg, config.engine_config)
        self._engine.start()
        self._sampling = config.sampling
        self._config = config

    def __call__(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        import numpy as np

        prompts = [str(p) for p in batch["prompt"]]
        if self._config.apply_chat_template:
            prompts = [self._tok.apply_chat_template(
                [{"role": "user", "content": p}]) for p in prompts]
        reqs = []
        eos = getattr(self._tok, "eos_id", None)
        sp = dict(self._sampling)
        if eos is not None:
            # ALWAYS stop at eos, including when the user supplied extra
            # stop ids — matching serve-side behavior (server.py)
            sp["stop_token_ids"] = tuple(
                sp.get("stop_token_ids", ())) + (eos,)
        for p in prompts:
            reqs.append(self._engine.submit(
                self._tok.encode(p), SamplingParams(**sp)))
        texts, token_lists = [], []
        for r in reqs:
            toks = []
            while True:
                item = r.out_queue.get(timeout=600)
                if item is None:
                    break
                if isinstance(item, Exception):
                    raise item
                toks.append(item)
            token_lists.append(toks)
            texts.append(self._tok.decode(toks))
        out_batch = dict(batch)
        out_batch["generated_text"] = texts
        out_batch["generated_tokens"] = np.array(
            [np.asarray(t, np.int64) for t in token_lists], dtype=object)
        return out_batch


def build_llm_processor(config: ProcessorConfig,
                        preprocess: Optional[Callable] = None,
                        postprocess: Optional[Callable] = None):
    """Returns Dataset -> Dataset.  Rows need a "prompt" column (or supply
    ``preprocess`` to create one)."""

    def processor(ds):
        if preprocess is not None:
            # row-wise hook, as in the reference's build_llm_processor
            ds = ds.map(preprocess)
        ds = ds.map_batches(
            _EngineUDF,
            fn_constructor_args=(config,),
            concurrency=config.concurrency,
            batch_size=config.batch_size,
            num_tpus=config.num_tpus,
            batch_format="numpy")
        if postprocess is not None:
            ds = ds.map(postprocess)
        return ds

    return processor
