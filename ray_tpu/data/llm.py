"""Batch LLM inference over Data: the build_llm_processor analog.

Reference analog: python/ray/llm/_internal/batch/processor/base.py:44
(Processor = a chain of stages applied to a Dataset) and the stage set under
_internal/batch/stages/ (ChatTemplateStage, TokenizeStage,
vLLMEngineStage, DetokenizeStage), surfaced as
ray.data.llm.build_llm_processor (data/llm.py:160). Ours runs the NATIVE
paged-attention engine inside an actor-pool map_batches stage (stateful:
one engine per actor, model loaded once), with tokenize/detokenize and
chat-template stages as plain task maps around it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional


@dataclasses.dataclass
class ProcessorConfig:
    """Engine-stage knobs (vLLMEngineProcessorConfig analog)."""
    model_config: Any = None          # llama.LlamaConfig
    params_checkpoint: Optional[str] = None
    seed: int = 0
    num_kv_blocks: int = 256
    block_size: int = 16
    max_batch_size: int = 8
    prefill_chunk: int = 128
    concurrency: int = 1              # engine actors
    batch_size: int = 16              # rows per engine call
    # sampling defaults, overridable per row via a "sampling_params" column
    max_tokens: int = 32
    temperature: float = 0.0


class _EngineStage:
    """Stateful actor callable: one engine per actor, continuous batching
    within each incoming block."""

    def __init__(self, config: ProcessorConfig):
        import jax

        from ray_tpu import models
        from ray_tpu.llm.engine import LLMEngine
        from ray_tpu.llm.model_runner import ModelRunner

        model_config = config.model_config or models.default_config()
        if config.params_checkpoint:
            from ray_tpu.train.checkpoint import Checkpoint

            params = Checkpoint(config.params_checkpoint).load_pytree()
        else:
            params = models.draw_params(model_config,
                                        jax.random.key(config.seed))
        runner = ModelRunner(model_config, params,
                             num_blocks=config.num_kv_blocks,
                             block_size=config.block_size,
                             chunk_size=config.prefill_chunk)
        self.engine = LLMEngine(runner,
                                max_batch_size=config.max_batch_size,
                                prefill_chunk=config.prefill_chunk)
        self.config = config
        # The actor pool may overlap transform() calls (max_concurrency);
        # the engine's donated-cache step is single-flight.
        import threading

        self._lock = threading.Lock()

    def __call__(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        with self._lock:
            return self._generate(batch)

    def _generate(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        from ray_tpu.llm.sampling import SamplingParams

        prompts = [list(map(int, p)) for p in batch["prompt_token_ids"]]
        per_row = batch.get("sampling_params")
        ids = []
        for i, p in enumerate(prompts):
            overrides = dict(per_row[i]) if per_row is not None else {}
            sp = SamplingParams(
                max_tokens=int(overrides.get("max_tokens",
                                             self.config.max_tokens)),
                temperature=float(overrides.get("temperature",
                                                self.config.temperature)),
                top_k=int(overrides.get("top_k", 0)),
                top_p=float(overrides.get("top_p", 1.0)),
                seed=overrides.get("seed"))
            ids.append(self.engine.add_request(p, sp))
        done: Dict[str, Any] = {}
        while self.engine.has_unfinished():
            for out in self.engine.step():
                if out.finished:
                    done[out.request_id] = out
        outs = [done[i] for i in ids]
        result = dict(batch)
        result["generated_token_ids"] = [o.output_token_ids for o in outs]
        result["finish_reason"] = [o.finish_reason for o in outs]
        return result


class Processor:
    """A reusable pipeline: ds -> preprocess -> tokenize -> engine ->
    detokenize -> postprocess. Call it on a Dataset to get a lazy Dataset
    with generation columns appended."""

    def __init__(self, config: ProcessorConfig, *, tokenizer=None,
                 chat_template=None,
                 preprocess: Optional[Callable[[Dict], Dict]] = None,
                 postprocess: Optional[Callable[[Dict], Dict]] = None):
        self.config = config
        self.tokenizer = tokenizer
        self.chat_template = chat_template
        self.preprocess = preprocess
        self.postprocess = postprocess

    # Each stage is a top-level-picklable callable built here.

    def _tokenize_stage(self):
        tokenizer, template = self.tokenizer, self.chat_template

        def tokenize(row: Dict) -> Dict:
            if "prompt_token_ids" in row:
                return row
            if "messages" in row and template is not None:
                row["prompt_token_ids"] = template.render(row["messages"])
            elif "prompt" in row and tokenizer is not None:
                row["prompt_token_ids"] = tokenizer.encode(row["prompt"])
            else:
                raise ValueError(
                    "row needs prompt_token_ids, or prompt+tokenizer, or "
                    "messages+chat_template")
            return row

        return tokenize

    def _detokenize_stage(self):
        tokenizer = self.tokenizer

        def detokenize(row: Dict) -> Dict:
            if tokenizer is not None and "generated_token_ids" in row:
                try:
                    row["generated_text"] = tokenizer.decode(
                        list(map(int, row["generated_token_ids"])))
                except Exception:
                    row["generated_text"] = None
            return row

        return detokenize

    def __call__(self, ds):
        if self.preprocess is not None:
            ds = ds.map(self.preprocess)
        ds = ds.map(self._tokenize_stage())
        config = self.config

        class _BoundEngineStage(_EngineStage):
            # Actor-pool classes are instantiated with no args; bind the
            # processor config via closure (cloudpickle carries it).
            def __init__(self):
                super().__init__(config)

        ds = ds.map_batches(_BoundEngineStage,
                            batch_size=self.config.batch_size,
                            compute="actors",
                            concurrency=self.config.concurrency)
        ds = ds.map(self._detokenize_stage())
        if self.postprocess is not None:
            ds = ds.map(self.postprocess)
        return ds


def build_llm_processor(config: ProcessorConfig, *, tokenizer=None,
                        chat_template=None, preprocess=None,
                        postprocess=None) -> Processor:
    """ray.data.llm.build_llm_processor analog (reference data/llm.py:160)."""
    return Processor(config, tokenizer=tokenizer, chat_template=chat_template,
                     preprocess=preprocess, postprocess=postprocess)
