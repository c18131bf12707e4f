"""Embedder UDFs — the TPU-native path is the default.

Re-design of ``python/pathway/xpacks/llm/embedders.py:64-330``
(``OpenAIEmbedder``/``LiteLLMEmbedder``/``SentenceTransformerEmbedder``/
``GeminiEmbedder``). The flagship here is ``TpuEmbedder``: a pure-JAX
transformer encoder (``pathway_tpu/models/embedder.py``) whose forward pass
runs bf16 on the MXU — documents are embedded on-device as they stream in,
instead of the reference's CPU sentence-transformers hot path.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from ...udfs import UDF, CacheStrategy, Executor

__all__ = [
    "BaseEmbedder",
    "TpuEmbedder",
    "SentenceTransformerEmbedder",
    "OpenAIEmbedder",
    "LiteLLMEmbedder",
    "GeminiEmbedder",
]


class BaseEmbedder(UDF):
    """text -> np.ndarray[float] column UDF. Subclasses implement
    ``_embed(text) -> np.ndarray``; ``get_embedding_dimension`` probes with
    a sample call (reference embedders.py BaseEmbedder)."""

    def __init__(
        self,
        *,
        cache_strategy: CacheStrategy | None = None,
        executor: Executor | None = None,
        **kwargs: Any,
    ):
        super().__init__(cache_strategy=cache_strategy, executor=executor)
        self.kwargs = kwargs

    def _embed(self, text: str, **kwargs: Any) -> np.ndarray:
        raise NotImplementedError

    def __wrapped__(self, text: str, **kwargs: Any) -> np.ndarray:
        return self._embed(text or ".", **{**self.kwargs, **kwargs})

    def get_embedding_dimension(self, **kwargs: Any) -> int:
        return len(self.__wrapped__(".", **kwargs))


class TpuEmbedder(BaseEmbedder):
    """Sentence embeddings computed by the in-framework JAX encoder on TPU
    (MXU bf16 matmuls, masked mean-pool, L2-norm). Single-row UDF calls are
    micro-batched through a shape-bucketed jitted forward, so streaming
    ingestion still hits the MXU with real batches."""

    def __init__(self, embedder: Any = None, *, model_path: str | None = None,
                 max_len: int | None = None, **kwargs: Any):
        """``model_path``: local directory with a MiniLM-class HF checkpoint
        (``pytorch_model.bin`` + ``vocab.txt``) — loads pretrained weights
        and the real WordPiece tokenizer (``models/embedder.py``
        ``Embedder.from_pretrained``). Default: deterministic-init encoder
        (self-contained, no checkpoint needed). ``max_len``: tokens a text is
        cut at; the encoder's own positions where none is given, as
        sentence-transformers' ``max_seq_length``."""
        super().__init__(**kwargs)
        if embedder is None:
            from ...models.embedder import Embedder

            if model_path is not None:
                embedder = Embedder.from_pretrained(model_path)
            else:
                embedder = Embedder()
        self.embedder = embedder
        self.max_len = max_len

    def _embed(self, text: str, **kwargs: Any) -> np.ndarray:
        return self.embedder.embed_texts([text], max_len=self.max_len)[0]

    def embed_batch(self, texts: list[str]) -> np.ndarray:
        return self.embedder.embed_texts(list(texts), max_len=self.max_len)


class SentenceTransformerEmbedder(BaseEmbedder):
    """reference embedders.py:217 — requires ``sentence_transformers``
    (not baked in; use TpuEmbedder)."""

    def __init__(self, model: str, call_kwargs: dict = {}, device: str = "cpu", **kwargs: Any):
        try:
            import sentence_transformers  # type: ignore[import-not-found]
        except ImportError as e:
            raise ImportError(
                "SentenceTransformerEmbedder requires the "
                "'sentence_transformers' package; TpuEmbedder is the native "
                "on-device equivalent"
            ) from e
        super().__init__(**kwargs)
        self.model = sentence_transformers.SentenceTransformer(model, device=device)
        self.call_kwargs = call_kwargs

    def _embed(self, text: str, **kwargs: Any) -> np.ndarray:
        return self.model.encode(text, **{**self.call_kwargs, **kwargs})


class OpenAIEmbedder(BaseEmbedder):
    """reference embedders.py:64 — requires ``openai`` + egress."""

    def __init__(self, model: str | None = "text-embedding-3-small", **kwargs: Any):
        try:
            import openai  # type: ignore[import-not-found]  # noqa: F401
        except ImportError as e:
            raise ImportError("OpenAIEmbedder requires the 'openai' package") from e
        super().__init__(**kwargs)
        self.model = model

    def _embed(self, text: str, **kwargs: Any) -> np.ndarray:
        import openai  # type: ignore[import-not-found]

        client = openai.OpenAI()
        ret = client.embeddings.create(
            input=[text], model=kwargs.pop("model", self.model), **kwargs
        )
        return np.asarray(ret.data[0].embedding)


class LiteLLMEmbedder(BaseEmbedder):
    """reference embedders.py:152 — requires ``litellm``."""

    def __init__(self, model: str | None = None, **kwargs: Any):
        try:
            import litellm  # type: ignore[import-not-found]  # noqa: F401
        except ImportError as e:
            raise ImportError("LiteLLMEmbedder requires the 'litellm' package") from e
        super().__init__(**kwargs)
        self.model = model

    def _embed(self, text: str, **kwargs: Any) -> np.ndarray:
        import litellm  # type: ignore[import-not-found]

        ret = litellm.embedding(
            input=[text], model=kwargs.pop("model", self.model), **kwargs
        )
        return np.asarray(ret.data[0]["embedding"])


class GeminiEmbedder(BaseEmbedder):
    """reference embedders.py:283 — requires ``google.generativeai``."""

    def __init__(self, model: str | None = "models/text-embedding-004", **kwargs: Any):
        try:
            import google.generativeai  # type: ignore[import-not-found]  # noqa: F401
        except ImportError as e:
            raise ImportError(
                "GeminiEmbedder requires the 'google-generativeai' package"
            ) from e
        super().__init__(**kwargs)
        self.model = model

    def _embed(self, text: str, **kwargs: Any) -> np.ndarray:
        import google.generativeai as genai  # type: ignore[import-not-found]

        ret = genai.embed_content(
            model=kwargs.pop("model", self.model), content=text, **kwargs
        )
        return np.asarray(ret["embedding"])
