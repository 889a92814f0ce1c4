"""agent_main.py with the timed path broken underneath: every emitted token
id is shifted by one where the engine produces it. For the test that sees
`correct` come out false; never part of a run."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from xllm_service_tpu.engine import engine as _engine  # noqa: E402

_emit = _engine.InferenceEngine._emit_tokens


def _emit_shifted(self, seq, tokens, lps):
    vocab = self.cfg.model.vocab_size
    return _emit(self, seq, [(t + 1) % vocab for t in tokens], lps)


_engine.InferenceEngine._emit_tokens = _emit_shifted

from chipbench import agent_main  # noqa: E402

if __name__ == "__main__":
    sys.exit(agent_main.main())
