"""Tokenizer tests: byte fallback, HF tokenizer.json (built
programmatically — zero downloads), chat templates, and the engine's
context-budget truncation."""

from pathlib import Path

import pytest

from adversarial_spec_tpu.engine.tokenizer import (
    ByteTokenizer,
    CHAT_TEMPLATES,
    GENERIC_CHAT_TEMPLATE,
    HFTokenizer,
    apply_chat_template,
    load_tokenizer,
)


@pytest.fixture(scope="module")
def hf_tokenizer_file(tmp_path_factory):
    from tokenizers import Tokenizer, models, pre_tokenizers, trainers

    tok = Tokenizer(models.BPE(unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    trainer = trainers.BpeTrainer(
        special_tokens=["<unk>", "<s>", "</s>", "<|eot_id|>"],
        vocab_size=200,
    )
    tok.train_from_iterator(
        [
            "the quick brown fox jumps over the lazy dog " * 3,
            "spec review critique agree revise document " * 3,
        ],
        trainer,
    )
    path = tmp_path_factory.mktemp("tok") / "tokenizer.json"
    tok.save(str(path))
    return str(path)


class TestByteTokenizer:
    def test_roundtrip(self):
        t = ByteTokenizer()
        ids = t.encode("hello ✓", add_bos=False)
        assert t.decode(ids) == "hello ✓"

    def test_bos_prepended(self):
        t = ByteTokenizer()
        assert t.encode("a")[0] == t.bos_id

    def test_out_of_range_ids_skipped(self):
        t = ByteTokenizer()
        assert t.decode([1, 400, 104 + 3, 105 + 3]) == "hi"

    def test_specials(self):
        t = ByteTokenizer()
        assert t.pad_id == 0 and t.bos_id == 1 and t.eos_ids == [2]


class TestHFTokenizer:
    def test_load_from_file_and_dir(self, hf_tokenizer_file):
        t = HFTokenizer(hf_tokenizer_file)
        assert t.vocab_size > 0
        import pathlib

        t2 = HFTokenizer(str(pathlib.Path(hf_tokenizer_file).parent))
        assert t2.vocab_size == t.vocab_size

    def test_roundtrip(self, hf_tokenizer_file):
        t = HFTokenizer(hf_tokenizer_file)
        ids = t.encode("critique the spec", add_bos=False)
        assert len(ids) >= 3
        assert t.decode(ids) == "critique the spec"

    def test_specials_detected(self, hf_tokenizer_file):
        t = HFTokenizer(hf_tokenizer_file)
        # <s> is a BOS candidate; </s> and <|eot_id|> are both EOS markers.
        assert t.bos_id is not None
        assert len(t.eos_ids) == 2

    def test_bos_prepended(self, hf_tokenizer_file):
        t = HFTokenizer(hf_tokenizer_file)
        with_bos = t.encode("spec")
        without = t.encode("spec", add_bos=False)
        assert with_bos == [t.bos_id] + without

    def test_factory(self, hf_tokenizer_file):
        assert isinstance(load_tokenizer(""), ByteTokenizer)
        assert isinstance(load_tokenizer(hf_tokenizer_file), HFTokenizer)


class TestChatTemplates:
    def test_generic_for_base_models(self):
        out = apply_chat_template("llama", "SYS", "USER", instruct=False)
        assert out == GENERIC_CHAT_TEMPLATE.format(system="SYS", user="USER")

    @pytest.mark.parametrize("family", sorted(CHAT_TEMPLATES))
    def test_family_templates_render(self, family):
        out = apply_chat_template(family, "SYS", "USER", instruct=True)
        assert "SYS" in out and "USER" in out
        assert out != GENERIC_CHAT_TEMPLATE.format(system="SYS", user="USER")

    def test_unknown_family_falls_back(self):
        out = apply_chat_template("falcon", "S", "U", instruct=True)
        assert out == GENERIC_CHAT_TEMPLATE.format(system="S", user="U")


class TestPromptTruncation:
    def test_long_prompt_truncated_to_context_budget(self, monkeypatch):
        """The engine must clamp prompts so prompt + max_new fits the
        model context, keeping the BOS and the prompt TAIL (the most
        recent document content)."""
        from adversarial_spec_tpu.engine.registry import (
            ModelSpec,
            save_registry_entry,
        )
        from adversarial_spec_tpu.engine.tpu import TpuEngine
        from adversarial_spec_tpu.engine.types import ChatRequest, SamplingParams

        save_registry_entry(
            ModelSpec(
                alias="small-ctx",
                family="llama",
                size="tiny",
                dtype="float32",
                max_seq_len=256,
            )
        )
        eng = TpuEngine()
        captured = {}
        import adversarial_spec_tpu.engine.tpu as tpu_mod

        real_generate = tpu_mod.generate

        def spy(params, cfg, prompts, **kw):
            captured["prompt_lens"] = [len(p) for p in prompts]
            return real_generate(params, cfg, prompts, **kw)

        monkeypatch.setattr(tpu_mod, "generate", spy)
        comp = eng.chat(
            [
                ChatRequest(
                    model="tpu://small-ctx", system="s", user="x " * 2000
                )
            ],
            SamplingParams(max_new_tokens=64, greedy=True),
        )[0]
        assert comp.ok, comp.error
        # budget = 256 - 64 = 192 tokens max for the prompt.
        assert captured["prompt_lens"][0] <= 192


class TestGoldenChatTemplates:
    """Golden parity: the engine's ``.format``-string CHAT_TEMPLATES vs
    the families' PUBLIC jinja chat templates rendered by transformers'
    OWN machinery (``render_jinja_template`` — the exact code
    ``PreTrainedTokenizer.apply_chat_template`` calls).
    A silent template mismatch on real instruct checkpoints
    would degrade critique quality with no failing test — this pins it.

    The vendored .jinja fixtures (tests/fixtures/chat_templates/) are
    the templates shipped in the public tokenizer_config.json of
    Llama-3-Instruct, Mistral-7B-Instruct-v0.2, gemma-2-it and
    Qwen2-Instruct. String-identical prompts imply token-identical ids
    under the family tokenizer (same text, same tokenizer); the BOS
    token the jinja templates inline is added by ``encode(add_bos=True)``
    on the engine side, so the assertion is
    ``bos_token + engine_render == hf_render``.

    Family conventions the engine must reproduce:
    - mistral / gemma-2 have NO system role — the public convention
      (mistral-common; gemma model card) folds the system prompt into
      the first user turn separated by a blank line;
    - qwen2 takes the system turn verbatim (no BOS token at all);
    - the debate engine always sends a non-empty system prompt
      (debate/prompts.py), so the empty-system default-injection path
      of qwen2's template is out of scope.
    """

    FIXTURES = Path(__file__).parent / "fixtures" / "chat_templates"
    SYSTEM = "You are a ruthless spec critic."
    USER = "# PRD\nShip the thing.\n\nCritique this spec."

    def _render_hf(self, fixture, messages, **special):
        ctu = pytest.importorskip(
            "transformers.utils.chat_template_utils",
            reason="needs transformers with render_jinja_template",
        )
        render_jinja_template = getattr(ctu, "render_jinja_template", None)
        if render_jinja_template is None:
            pytest.skip("transformers too old: no render_jinja_template")

        template = (self.FIXTURES / fixture).read_text().rstrip("\n")
        rendered, _ = render_jinja_template(
            conversations=[messages],
            chat_template=template,
            add_generation_prompt=True,
            **special,
        )
        return rendered[0] if isinstance(rendered, list) else rendered

    @pytest.mark.parametrize(
        "family,fixture,bos",
        [
            ("llama", "llama3.jinja", "<|begin_of_text|>"),
            ("mistral", "mistral.jinja", "<s>"),
            ("gemma2", "gemma2.jinja", "<bos>"),
            ("qwen2", "qwen2.jinja", ""),
        ],
    )
    def test_engine_matches_public_template(self, family, fixture, bos):
        if family in ("mistral", "gemma2"):
            # No system role in the public template: fold into the
            # first user turn (the engine template does the same).
            messages = [
                {
                    "role": "user",
                    "content": f"{self.SYSTEM}\n\n{self.USER}",
                }
            ]
        else:
            messages = [
                {"role": "system", "content": self.SYSTEM},
                {"role": "user", "content": self.USER},
            ]
        hf = self._render_hf(
            fixture, messages, bos_token=bos, eos_token="</s>"
        )
        engine = apply_chat_template(
            family, self.SYSTEM, self.USER, instruct=True
        )
        assert bos + engine == hf
