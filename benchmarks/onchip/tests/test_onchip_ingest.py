"""The ingest cell's run at a tiny size on the CPU, and its faults."""

import numpy as np
import pytest

from onchip_testlib import run_cell, tiny_checkout

CELL = "bgevl-large.ingest"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("onchip-ingest"))


def test_ingest_cell_result_line(checkout, capsys):
    line = run_cell(checkout, CELL, capsys)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"ingest_fps", "setup_s"}
    assert line["metrics"]["ingest_fps"]["unit"] == "frames/s"
    assert set(line["checks"]) == {"segment_mismatch", "image_emb_gap"}


def test_ingest_cell_traced(checkout, capsys):
    line = run_cell(checkout, CELL, capsys, trace=True)
    assert line["correct"] is True
    for name in ("ingest.segment_ms", "ingest.cluster_ms",
                 "ingest.embed_insert_ms", "ingest.mfu"):
        assert line["metrics"][name]["value"] > 0
    assert line["device"]["window_s"] > 0


def test_unchanged_state_is_not_correct(checkout, capsys, monkeypatch):
    """A step that returns its state unchanged: the arena's tick scatter
    never lands, so the device keeps its pre-window rows."""
    from repro.core.memory import MemoryArena
    monkeypatch.setattr(MemoryArena, "_flush", lambda self, pending: 0)
    line = run_cell(checkout, CELL, capsys)
    assert line["correct"] is False


def test_altered_embeddings_are_not_correct(checkout, capsys, monkeypatch):
    """An answer altered where it is produced: MEM's image embeddings
    nudged before they are stored."""
    from repro.core.pipeline import MEMEmbedder
    real = MEMEmbedder.embed_frames

    def altered(self, frames, aux_texts=None, frame_ids=None):
        e = real(self, frames, aux_texts, frame_ids=frame_ids)
        e = e + 0.2 * np.roll(e, 1, axis=-1)
        return e / np.linalg.norm(e, axis=-1, keepdims=True)

    monkeypatch.setattr(MEMEmbedder, "embed_frames", altered)
    line = run_cell(checkout, CELL, capsys)
    assert line["correct"] is False
    assert line["checks"]["image_emb_gap"]["value"] > \
        line["checks"]["image_emb_gap"]["limit"]
