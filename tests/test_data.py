"""Host data layer tests: tokenizer, vocabulary, COCO index, DataSet."""

import os
import time

import numpy as np
import pytest

from sat_tpu.data import (
    CocoCaptions,
    DataSet,
    Vocabulary,
    tokenize,
    tokenize_no_punct,
)
from sat_tpu.data.dataset import prepare_eval_data, prepare_train_data


class TestTokenizer:
    def test_basic_caption(self):
        assert tokenize("A man riding a horse.") == [
            "a", "man", "riding", "a", "horse", ".",
        ]

    def test_commas_and_contractions(self):
        assert tokenize("It's a dog, isn't it?") == [
            "it", "'s", "a", "dog", ",", "is", "n't", "it", "?",
        ]

    def test_no_punct_variant(self):
        assert tokenize_no_punct("A man, riding; a horse.") == [
            "a", "man", "riding", "a", "horse",
        ]

    def test_numbers_keep_commas(self):
        # Treebank keeps commas inside numbers
        assert "1,000" in tokenize("there are 1,000 birds.")

    def test_ellipsis_and_quotes(self):
        toks = tokenize('he said "stop" ... now.')
        assert "``" in toks and "''" in toks and "..." in toks


class TestVocabulary:
    def test_build_order_and_start_token(self):
        v = Vocabulary(size=50)
        v.build(["a dog and a cat.", "a dog runs."])
        assert v.words[0] == "<start>"
        # 'a' (3) and '.' (2) are the most frequent
        assert v.words[1] == "a"
        assert v.word2idx["a"] == 1

    def test_shrinks_to_corpus(self):
        v = Vocabulary(size=5000)
        v.build(["a dog.", "a cat."])
        assert v.size == len(set("a dog . cat".split())) + 1

    def test_roundtrip_and_sentence(self, tmp_path):
        v = Vocabulary(size=100)
        v.build(["a man riding a horse on the beach."])
        p = str(tmp_path / "vocab.csv")
        v.save(p)
        v2 = Vocabulary(size=100, save_file=p)
        assert list(v2.words) == list(v.words)
        idxs = v2.process_sentence("a man riding a horse.")
        assert v2.get_sentence(idxs) == "a man riding a horse."

    def test_get_sentence_truncates_at_period(self):
        v = Vocabulary(size=100)
        v.build(["a dog runs fast."])
        idxs = v.process_sentence("a dog. runs fast.")
        assert v.get_sentence(idxs) == "a dog."

    def test_get_sentence_appends_period(self):
        v = Vocabulary(size=100)
        v.build(["a dog runs."])
        idxs = v.process_sentence("a dog runs")
        assert v.get_sentence(idxs) == "a dog runs."

    @pytest.mark.skipif(
        not os.path.exists("/root/reference/data/vocabulary.csv"),
        reason="reference fixture not mounted",
    )
    def test_loads_reference_csv_format(self):
        v = Vocabulary(size=5000, save_file="/root/reference/data/vocabulary.csv")
        assert v.words[0] == "<start>"
        assert "." in v.word2idx


class TestCoco:
    def test_index_and_normalization(self, coco_fixture):
        coco = CocoCaptions(coco_fixture["train_json"])
        assert len(coco.imgs) == 12
        assert len(coco.anns) == 24
        for ann in coco.anns.values():
            assert ann["caption"].endswith(".")
            assert ann["caption"] == ann["caption"].lower()

    def test_max_ann_cap(self, coco_fixture):
        coco = CocoCaptions(coco_fixture["train_json"], max_ann_num=5)
        assert len(coco.anns) == 5

    def test_filter_by_cap_len(self, coco_fixture):
        coco = CocoCaptions(coco_fixture["train_json"])
        coco.filter_by_cap_len(6)
        for ann in coco.anns.values():
            assert len(tokenize(ann["caption"])) <= 6

    def test_filter_by_words(self, coco_fixture):
        coco = CocoCaptions(coco_fixture["train_json"])
        vocab = {"a", "man", "riding", "horse", "on", "the", "beach", "."}
        coco.filter_by_words(vocab)
        assert all(
            set(tokenize(a["caption"])) <= vocab for a in coco.anns.values()
        )
        # images with no surviving annotations are dropped
        for img_id in coco.imgs:
            assert coco.img_to_anns.get(img_id)

    def test_load_results_validates(self, coco_fixture):
        coco = CocoCaptions(coco_fixture["val_json"])
        res = coco.load_results(
            [{"image_id": 1, "caption": "a dog."}, {"image_id": 2, "caption": "a cat."}]
        )
        assert len(res.imgs) == 2
        with pytest.raises(ValueError):
            coco.load_results([{"image_id": 99999, "caption": "x."}])


class TestDataSet:
    def test_fake_count_padding(self):
        n, bs = 10, 4
        ds = DataSet(
            list(range(n)), [f"f{i}" for i in range(n)], bs,
            np.zeros((n, 20), np.int32), np.ones((n, 20), np.float32),
            is_train=True, shuffle=False, seed=0,
        )
        assert ds.num_batches == 3
        assert ds.fake_count == 2
        batches = list(ds)
        assert len(batches) == 3
        for files, words, masks in batches:
            assert len(files) == bs and words.shape == (bs, 20)

    def test_shuffle_on_reset(self):
        n = 32
        ds = DataSet(list(range(n)), [str(i) for i in range(n)], 4,
                     np.zeros((n, 20)), np.ones((n, 20)),
                     is_train=True, shuffle=True, seed=1)
        order1 = list(ds.idxs)
        ds.reset()
        assert list(ds.idxs) != order1


class TestPrepare:
    def test_prepare_train_data(self, coco_fixture):
        cfg = coco_fixture["config"]
        ds = prepare_train_data(cfg)
        assert ds.count == 24
        files, words, masks = ds.next_batch()
        assert words.shape == (cfg.batch_size, cfg.max_caption_length)
        assert masks.max() == 1.0
        # caches were written and reload cleanly
        assert os.path.exists(cfg.temp_annotation_file)
        assert os.path.exists(cfg.temp_data_file)
        ds2 = prepare_train_data(cfg)
        assert ds2.count == ds.count

    def test_prepare_eval_data(self, coco_fixture):
        cfg = coco_fixture["config"]
        coco, ds, vocab = prepare_eval_data(cfg)
        assert ds.count == cfg.max_eval_ann_num
        assert not ds.is_train
        assert vocab.words[0] == "<start>"

    def test_image_loader(self, coco_fixture):
        from sat_tpu.data import ImageLoader

        loader = ImageLoader()
        files = [
            os.path.join(coco_fixture["train_img_dir"], f)
            for f in sorted(os.listdir(coco_fixture["train_img_dir"]))[:3]
        ]
        batch = loader.load_images(files)
        assert batch.shape == (3, 224, 224, 3)
        assert batch.dtype == np.float32

    def test_prefetch_loader(self, coco_fixture):
        from sat_tpu.data import PrefetchLoader

        cfg = coco_fixture["config"]
        ds = prepare_train_data(cfg)
        seen = 0
        for batch in PrefetchLoader(ds, num_workers=2, prefetch_depth=2):
            assert batch["images"].shape == (cfg.batch_size, 224, 224, 3)
            assert batch["word_idxs"].shape == (cfg.batch_size, 20)
            seen += 1
        assert seen == ds.num_batches


hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402


class TestDataSetProperties:
    """Property-based invariants of the batch iterator (hypothesis)."""

    @given(
        n=st.integers(1, 64),
        batch_size=st.integers(1, 16),
        shuffle=st.booleans(),
        seed=st.integers(0, 5),
    )
    @settings(max_examples=40, deadline=None)
    def test_every_epoch_covers_every_item_exactly_once(
        self, n, batch_size, shuffle, seed
    ):
        ds = DataSet(
            list(range(n)), [f"f{i}" for i in range(n)], batch_size,
            shuffle=shuffle, seed=seed,
        )
        for _ in range(2):
            files = []
            batches = 0
            for batch in ds:
                assert len(batch) == batch_size          # static shapes
                files.extend(batch)
                batches += 1
            assert batches == ds.num_batches
            # the non-pad portion (everything but the final batch's
            # fake_count tail) is exactly a permutation of the dataset
            core = [f for b in range(ds.num_batches - 1)
                    for f in files[b * batch_size:(b + 1) * batch_size]]
            tail_real = files[(ds.num_batches - 1) * batch_size:][
                : n - (ds.num_batches - 1) * batch_size
            ]
            assert sorted(core + tail_real) == sorted(f"f{i}" for i in range(n))

    @given(
        n=st.integers(2, 48),
        batch_size=st.integers(1, 8),
        epoch=st.integers(0, 3),
        seed=st.integers(0, 3),
        offset_raw=st.integers(0, 63),
    )
    @settings(max_examples=40, deadline=None)
    def test_seek_replays_any_epoch_tail(
        self, n, batch_size, epoch, seed, offset_raw
    ):
        mk = lambda: DataSet(  # noqa: E731
            list(range(n)), [f"f{i}" for i in range(n)], batch_size,
            shuffle=True, seed=seed,
        )
        ds = mk()
        epochs = []
        for _ in range(epoch + 1):
            epochs.append([tuple(b) for b in ds])
        offset = offset_raw % ds.num_batches   # any valid batch offset
        ds2 = mk()
        ds2.seek(epoch, offset)
        assert [tuple(b) for b in ds2] == epochs[epoch][offset:]


def test_prefetch_loader_surfaces_worker_errors(coco_fixture, tmp_path):
    """A missing/corrupt image mid-epoch must raise on the consumer side
    (not hang the queue or silently skip the batch)."""
    import shutil

    from sat_tpu.data import PrefetchLoader
    from sat_tpu.data.images import PrefetchDecodeError

    cfg = coco_fixture["config"]
    # private image dir so deleting a file can't break sibling tests
    img_dir = tmp_path / "images"
    shutil.copytree(cfg.train_image_dir, img_dir)
    cfg = cfg.replace(
        train_image_dir=str(img_dir),
        temp_annotation_file=str(tmp_path / "anns.csv"),
        temp_data_file=str(tmp_path / "data.npy"),
    )
    ds = prepare_train_data(cfg)
    victim = sorted(img_dir.iterdir())[2]
    victim.unlink()
    with pytest.raises(PrefetchDecodeError) as raised:
        for _ in PrefetchLoader(ds, num_workers=2, prefetch_depth=2):
            pass
    err = raised.value
    # the read failure is the cause; the wrapper says which record broke
    assert isinstance(err.__cause__, FileNotFoundError)
    assert err.image_file == str(victim)
    assert err.batch_index >= 0 and 0 <= err.row < cfg.batch_size
    message = str(err)
    assert str(victim) in message
    assert f"(batch {err.batch_index}, row {err.row})" in message


def test_prefetch_loader_abandoned_iterator_releases_producer(coco_fixture):
    """Breaking out of the loader mid-epoch must stop the producer thread
    (the bounded put aborts on the consumer-gone signal) — an abandoned
    iterator may not pin a thread or deadlock interpreter exit."""
    import threading

    from sat_tpu.data import PrefetchLoader

    ds = prepare_train_data(coco_fixture["config"])
    before = threading.active_count()
    it = iter(PrefetchLoader(ds, num_workers=2, prefetch_depth=1))
    next(it)
    it.close()  # generator finalizer sets the stop event
    deadline = time.time() + 10
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before


class TestDevicePreprocess:
    """uint8 raw feed + on-device mean-sub (config.device_preprocess) must
    be bitwise-equal to the host path: the resize already runs on the
    uint8 image in both modes (reference utils/misc.py:22-27 order), so
    deferring astype(float32)−mean to the accelerator changes nothing
    numerically while shrinking the feed 4x."""

    def _jpg(self, tmp_path):
        import cv2

        rng = np.random.default_rng(0)
        f = str(tmp_path / "img.jpg")
        cv2.imwrite(f, rng.integers(0, 255, (48, 64, 3), dtype=np.uint8))
        return f

    def test_raw_loader_matches_host_preprocess(self, tmp_path):
        from sat_tpu.data.images import ILSVRC_2012_MEAN, ImageLoader

        f = self._jpg(tmp_path)
        host = ImageLoader(size=32).load_image(f)
        raw = ImageLoader(size=32, raw=True).load_image(f)
        assert raw.dtype == np.uint8
        np.testing.assert_array_equal(
            host, raw.astype(np.float32) - ILSVRC_2012_MEAN
        )

    def test_encode_uint8_feed_bitwise_equals_float_feed(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from sat_tpu.config import Config
        from sat_tpu.data.images import ILSVRC_2012_MEAN
        from sat_tpu.models.captioner import encode, init_variables

        cfg = Config(
            image_size=32, vocabulary_size=30, dim_embedding=8,
            num_lstm_units=8, dim_initialize_layer=8, dim_attend_layer=8,
            dim_decode_layer=8, compute_dtype="float32",
        )
        variables = init_variables(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(1)
        raw = rng.integers(0, 255, (2, 32, 32, 3), dtype=np.uint8)
        host = raw.astype(np.float32) - ILSVRC_2012_MEAN

        ctx_raw, _ = encode(variables, cfg, jnp.asarray(raw), train=False)
        ctx_host, _ = encode(variables, cfg, jnp.asarray(host), train=False)
        np.testing.assert_array_equal(np.asarray(ctx_raw), np.asarray(ctx_host))
