"""Live RAG document store served over REST — the Adaptive-RAG template's
serving path (reference ``templates/rag``), TPU-native end to end.

Watches a directory of documents (txt/pdf/docx/pptx/html/markdown — the
local parser auto-dispatches by content), embeds them on the accelerator
(MiniLM-class encoder, bf16 on the MXU), maintains a brute-force KNN
index as one device-resident block (exact search = one matmul + top_k),
and serves:

    POST /v1/retrieve   {"query": "...", "k": 3}
    POST /v1/statistics {}
    POST /v1/inputs     {}

Run:

    python examples/rag_server/serve.py --docs ./docs --port 8666

then drop files into ./docs while it runs — the index updates live, and
queries immediately see new documents (one dataflow, no rebuild).
"""

from __future__ import annotations

import argparse
import os

# Static-analysis suppressions (`pathway-tpu lint examples/`):
# - a document store's index/state is SUPPOSED to grow with the corpus —
#   there is no temporal cutoff to add;
# - the parse/split/embed UDFs run arbitrary document-processing Python
#   per row by design (they are io-heavy, not expression-shaped).
# pathway: ignore[unbounded-state, perrow-udf]

import pathway_tpu as pw
from pathway_tpu.stdlib.indexing.nearest_neighbors import BruteForceKnnFactory
from pathway_tpu.xpacks.llm.document_store import DocumentStore
from pathway_tpu.xpacks.llm.embedders import TpuEmbedder
from pathway_tpu.xpacks.llm.parsers import ParseLocal
from pathway_tpu.xpacks.llm.servers import DocumentStoreServer
from pathway_tpu.xpacks.llm.splitters import TokenCountSplitter


def build_server(
    docs_dir: str, host: str, port: int, max_tokens: int = 256
) -> DocumentStoreServer:
    """The served pipeline: watched directory -> parse -> split -> embed ->
    index -> REST (``chip_smoke.py`` drives exactly this on the chip)."""
    # a watch directory that does not exist yet is an empty corpus, not an
    # error — create it so `serve.py` works (and lints) out of the box
    os.makedirs(docs_dir, exist_ok=True)
    docs = pw.io.fs.read(
        docs_dir, format="binary", mode="streaming", with_metadata=True,
    )

    embedder = TpuEmbedder()
    store = DocumentStore(
        docs,
        BruteForceKnnFactory(
            dimensions=embedder.embedder.cfg.dim,
            embedder=embedder.embedder,
        ),
        parser=ParseLocal(),
        splitter=TokenCountSplitter(max_tokens=max_tokens),
    )
    return DocumentStoreServer(host, port, store)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", default="docs", help="directory to watch")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8666)
    ap.add_argument("--max-tokens", type=int, default=256)
    args = ap.parse_args()

    server = build_server(args.docs, args.host, args.port, args.max_tokens)
    print(f"serving on http://{args.host}:{args.port}/v1/retrieve")
    server.run()


if __name__ == "__main__":
    main()
